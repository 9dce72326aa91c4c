/* Generated fixed-point reference — see repro.ir.cgen. Do not edit. */
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

static int32_t add32(int32_t a, int32_t b) {
    return (int32_t)((uint32_t)a + (uint32_t)b);
}
static int32_t sub32(int32_t a, int32_t b) {
    return (int32_t)((uint32_t)a - (uint32_t)b);
}
static int32_t neg32(int32_t a) { return (int32_t)(0u - (uint32_t)a); }
static int32_t min32(int32_t a, int32_t b) { return a < b ? a : b; }
static int32_t max32(int32_t a, int32_t b) { return a > b ? a : b; }
static int32_t abs32(int32_t a) { return a < 0 ? neg32(a) : a; }
static int32_t sign32(int32_t a) { return a > 0 ? 1 : (a < 0 ? -1 : 0); }
static int32_t shl32(int32_t x, int32_t k) {
    if (k >= 32 || k < 0) return 0;
    return (int32_t)((uint32_t)x << k);
}
static int32_t asr32(int32_t x, int32_t k) {
    if (k < 0) k = 0;
    if (k >= 32) return x < 0 ? -1 : 0;
    if (k == 0) return x;
    {
        uint32_t s = (uint32_t)x >> k;
        if (x < 0) s |= ~(uint32_t)0 << (32 - k);
        return (int32_t)s;
    }
}
static int32_t shrl32(int32_t x, int32_t k) {
    if (k >= 32 || k < 0) return 0;
    return (int32_t)((uint32_t)x >> k);
}
static long clamp_start(long s, long dim, long size) {
    if (s < 0) s = 0;
    if (s > dim - size) s = dim - size;
    return s;
}

static const int32_t rom0_c[80] = {
    2, 0, -7, 1, 17, -10, -25, 20, 20, -25, -10, 17,
    1, -7, 0, 2, -2, 2, 1, -12, 11, 9, -29, 16,
    16, -29, 9, 11, -12, 1, 2, -2, 0, -3, 6, -5,
    -7, 22, -28, 12, 12, -28, 22, -7, -5, 6, -3, 0,
    0, 0, -4, 10, -19, 22, -20, 7, 7, -20, 22, -19,
    10, -4, 0, 0, -6, 7, -14, 21, -26, 25, -19, 7,
    7, -19, 25, -26, 21, -14, 7, -6
};
static const int32_t rom1_c[6] = {
    -1, 8, 56, 56, 8, -1
};
static const int32_t rom2_c[30] = {
    0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
    0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
    0, 0, 0, 0, 0, 0
};
static const int32_t rom3_c[30] = {
    -3, -3, -3, -3, -3, -3, -3, -3, -3, -3, -3, -3,
    -3, -3, -3, -3, -3, -3, -3, -3, -3, -3, -3, -3,
    -3, -3, -3, -3, -3, -3
};
static const int32_t rom4_c[30] = {
    -4, -4, -4, -4, -4, -4, -4, -4, -4, -4, -4, -4,
    -4, -4, -4, -4, -4, -4, -4, -4, -4, -4, -4, -4,
    -4, -4, -4, -4, -4, -4
};
static const int32_t rom5_c[300] = {
    13, 3, 3, 2, 3, 11, 12, 2, 15, 0, 1, 8,
    2, 9, 15, 11, 11, 5, 13, 10, 4, 3, 12, 13,
    13, 5, 13, 15, 0, 10, 15, 2, 4, 15, 12, 6,
    0, 3, 0, 15, 14, 10, 8, 14, 6, 5, 12, 6,
    11, 9, 0, 15, 12, 15, 15, 3, 14, 2, 13, 6,
    13, 10, 4, 9, 0, 3, 6, 15, 0, 2, 1, 1,
    8, 15, 14, 11, 11, 12, 12, 7, 4, 15, 7, 0,
    4, 15, 5, 16, 4, 14, 3, 14, 10, 9, 15, 5,
    1, 12, 6, 1, 0, 10, 4, 15, 2, 14, 3, 1,
    9, 4, 12, 12, 6, 15, 0, 11, 13, 10, 14, 6,
    0, 8, 3, 14, 13, 6, 13, 6, 1, 3, 12, 13,
    10, 9, 1, 12, 5, 15, 6, 10, 15, 9, 2, 8,
    4, 6, 2, 7, 11, 2, 5, 0, 10, 3, 4, 6,
    5, 15, 12, 0, 11, 0, 10, 4, 1, 7, 11, 9,
    0, 13, 4, 3, 1, 12, 7, 13, 2, 13, 13, 5,
    0, 6, 2, 16, 15, 2, 11, 13, 6, 14, 9, 14,
    10, 13, 15, 1, 11, 1, 2, 1, 4, 9, 13, 3,
    2, 3, 4, 7, 0, 3, 11, 12, 10, 11, 14, 2,
    15, 0, 3, 13, 5, 3, 9, 8, 11, 15, 8, 3,
    4, 10, 8, 7, 2, 15, 8, 5, 2, 1, 4, 8,
    3, 1, 2, 13, 4, 5, 11, 13, 7, 8, 9, 7,
    15, 2, 10, 6, 13, 11, 7, 3, 13, 4, 13, 8,
    3, 0, 11, 10, 10, 3, 9, 11, 3, 12, 12, 9,
    5, 9, 12, 6, 14, 10, 4, 5, 6, 12, 6, 9,
    10, 14, 12, 2, 5, 3, 11, 14, 13, 14, 5, 15
};
static const int32_t rom6_c[300] = {
    0, 0, 9, 5, 3, 2, 2, 9, 1, 15, 6, 13,
    1, 3, 5, 10, 1, 7, 2, 0, 2, 6, 14, 12,
    2, 4, 12, 15, 5, 8, 4, 0, 6, 13, 1, 3,
    0, 0, 5, 0, 0, 0, 6, 3, 10, 2, 15, 11,
    7, 12, 13, 2, 12, 1, 9, 0, 5, 11, 3, 13,
    6, 8, 13, 13, 15, 12, 14, 2, 11, 11, 1, 14,
    8, 7, 8, 12, 11, 6, 5, 12, 0, 11, 2, 8,
    4, 14, 0, 13, 3, 10, 3, 8, 8, 8, 7, 12,
    4, 9, 1, 12, 11, 11, 2, 10, 2, 7, 11, 14,
    11, 2, 0, 10, 2, 8, 4, 6, 7, 15, 6, 1,
    4, 7, 14, 5, 5, 3, 12, 8, 7, 11, 3, 14,
    0, 10, 8, 14, 1, 11, 6, 12, 9, 12, 14, 8,
    1, 3, 1, 13, 7, 7, 0, 7, 1, 12, 6, 7,
    6, 5, 11, 7, 8, 11, 2, 11, 11, 4, 0, 13,
    12, 4, 10, 6, 2, 9, 5, 8, 15, 1, 2, 12,
    1, 13, 8, 15, 10, 7, 13, 6, 2, 8, 13, 12,
    13, 10, 3, 5, 11, 5, 10, 6, 4, 0, 14, 1,
    4, 8, 6, 0, 6, 0, 0, 16, 9, 6, 15, 11,
    9, 4, 4, 3, 11, 4, 15, 13, 7, 8, 0, 14,
    0, 13, 8, 15, 5, 13, 0, 2, 9, 10, 12, 1,
    12, 12, 8, 10, 7, 3, 11, 12, 13, 16, 2, 6,
    9, 5, 10, 12, 8, 12, 11, 3, 9, 13, 11, 4,
    2, 4, 1, 9, 5, 0, 3, 14, 2, 8, 2, 0,
    2, 8, 1, 3, 5, 15, 14, 2, 15, 2, 5, 5,
    2, 7, 6, 3, 4, 7, 6, 11, 15, 14, 10, 3
};
static const int32_t rom7_c[10] = {
    0, 0, 0, 0, 0, 0, 0, 0, 0, 0
};
static const int32_t rom8_lit[1] = {
    1
};
static const int32_t rom9_lit[1] = {
    0
};
static const int32_t rom10_lit[1] = {
    16399
};
static const int32_t rom11_lit[1] = {
    1039
};
static const int32_t rom12_lit[1] = {
    -512
};
static const int32_t rom13_lit[1] = {
    511
};
static const int32_t rom14_lit[1] = {
    512
};
static const int32_t rom15_lit[1] = {
    16389
};
static const int32_t rom16_lit[1] = {
    1029
};
static const int32_t rom17_lit[1] = {
    -128
};
static const int32_t rom18_lit[1] = {
    127
};
static const int32_t rom19_lit[1] = {
    8207
};
static const int32_t rom20_lit[1] = {
    8197
};
static const int32_t rom21_lit[1] = {
    4111
};
static const int32_t rom22_lit[1] = {
    2
};
static const int32_t rom23_lit[1] = {
    4101
};
static const int32_t rom24_lit[1] = {
    2063
};
static const int32_t rom25_lit[1] = {
    3
};
static const int32_t rom26_lit[1] = {
    2053
};
static const int32_t rom27_lit[1] = {
    1015
};
static const int32_t rom28_lit[1] = {
    4
};
static const int32_t rom29_lit[1] = {
    1005
};
static const int32_t rom30_lit[1] = {
    515
};
static const int32_t rom31_lit[1] = {
    5
};
static const int32_t rom32_lit[1] = {
    256
};
static const int32_t rom33_lit[1] = {
    32
};

static int32_t r0[16000];
static const int32_t *const r1 = rom0_c;
static const int32_t *const r2 = rom1_c;
static const int32_t *const r3 = rom2_c;
static const int32_t *const r4 = rom3_c;
static const int32_t *const r5 = rom4_c;
static const int32_t *const r6 = rom5_c;
static const int32_t *const r7 = rom6_c;
static const int32_t *const r8 = rom7_c;
static const int32_t *const r9 = rom8_lit;
static int32_t r10[16000];
static int32_t r11[80];
static int32_t r12[80];
static const int32_t *const r13 = rom9_lit;
static int32_t r14[1];
static int32_t r15[16015];
static int32_t r16[1];
static int32_t r17[16399];
static int32_t r18[1024];
static int32_t r19[1024];
static int32_t r20[16];
static int32_t r21[16];
static int32_t r22[16384];
static int32_t r23[16];
static int32_t r24[16];
static int32_t r25[16399];
static int32_t r26[16384];
static int32_t r27[80];
static int32_t r28[1];
static uint8_t r29[1];
static const int32_t *const r30 = rom10_lit;
static int32_t r31[1];
static int32_t r32[1];
static int32_t r33[1039];
static uint8_t r34[16384];
static const int32_t *const r35 = rom11_lit;
static int32_t r36[16384];
static int32_t r37[16384];
static int32_t r38[16384];
static int32_t r39[16384];
static int32_t r40[16384];
static int32_t r41[81920];
static const int32_t *const r42 = rom12_lit;
static const int32_t *const r43 = rom13_lit;
static int32_t r44[1];
static int32_t r45[81920];
static int32_t r46[1];
static int32_t r47[81920];
static int32_t r48[81920];
static int32_t r49[1];
static int32_t r50[81920];
static int32_t r51[1];
static int32_t r52[81920];
static int32_t r53[81920];
static int32_t r54[5120];
static const int32_t *const r55 = rom14_lit;
static int32_t r56[5120];
static int32_t r57[81920];
static int32_t r58[1];
static int32_t r59[1];
static int32_t r60[5120];
static int32_t r61[5120];
static int32_t r62[1];
static int32_t r63[5120];
static int32_t r64[5120];
static int32_t r65[5120];
static int32_t r66[81920];
static int32_t r67[81920];
static int32_t r68[5120];
static int32_t r69[81920];
static int32_t r70[5120];
static int32_t r71[81920];
static int32_t r72[81920];
static int32_t r73[5120];
static int32_t r74[5120];
static uint8_t r75[5120];
static int32_t r76[5120];
static int32_t r77[5120];
static int32_t r78[1];
static int32_t r79[5120];
static int32_t r80[5120];
static int32_t r81[81920];
static int32_t r82[5120];
static int32_t r83[5120];
static int32_t r84[81920];
static int32_t r85[1];
static int32_t r86[1];
static int32_t r87[5120];
static int32_t r88[5120];
static int32_t r89[1];
static int32_t r90[5120];
static int32_t r91[5120];
static int32_t r92[5120];
static int32_t r93[81920];
static int32_t r94[81920];
static int32_t r95[5120];
static int32_t r96[81920];
static int32_t r97[5120];
static int32_t r98[81920];
static int32_t r99[81920];
static int32_t r100[5120];
static int32_t r101[5120];
static uint8_t r102[5120];
static int32_t r103[5120];
static int32_t r104[5120];
static int32_t r105[1];
static int32_t r106[5120];
static int32_t r107[5120];
static int32_t r108[5120];
static int32_t r109[81920];
static int32_t r110[81920];
static int32_t r111[81920];
static int32_t r112[80000];
static int32_t r113[80000];
static int32_t r114[80000];
static int32_t r115[5];
static int32_t r116[5];
static int32_t r117[16000];
static int32_t r118[6];
static int32_t r119[6];
static int32_t r120[1];
static int32_t r121[16005];
static int32_t r122[1];
static int32_t r123[16389];
static int32_t r124[1024];
static int32_t r125[1024];
static int32_t r126[6];
static int32_t r127[6];
static int32_t r128[6144];
static int32_t r129[16];
static int32_t r130[16];
static int32_t r131[16389];
static int32_t r132[6144];
static int32_t r133[6];
static int32_t r134[1];
static uint8_t r135[1];
static const int32_t *const r136 = rom15_lit;
static int32_t r137[1];
static int32_t r138[1];
static int32_t r139[1029];
static uint8_t r140[6144];
static const int32_t *const r141 = rom16_lit;
static int32_t r142[6144];
static int32_t r143[6144];
static int32_t r144[6144];
static int32_t r145[6144];
static int32_t r146[6144];
static int32_t r147[6144];
static int32_t r148[1];
static int32_t r149[6144];
static int32_t r150[1];
static int32_t r151[6144];
static int32_t r152[6144];
static int32_t r153[1];
static int32_t r154[6144];
static int32_t r155[1];
static int32_t r156[6144];
static int32_t r157[6144];
static int32_t r158[1024];
static int32_t r159[1024];
static int32_t r160[6144];
static int32_t r161[1];
static int32_t r162[1];
static int32_t r163[1024];
static int32_t r164[1024];
static int32_t r165[1];
static int32_t r166[1024];
static int32_t r167[1024];
static int32_t r168[1024];
static int32_t r169[6144];
static int32_t r170[6144];
static int32_t r171[1024];
static int32_t r172[6144];
static int32_t r173[1024];
static int32_t r174[6144];
static int32_t r175[6144];
static int32_t r176[1024];
static int32_t r177[1024];
static uint8_t r178[1024];
static int32_t r179[1024];
static int32_t r180[1024];
static int32_t r181[1];
static int32_t r182[1024];
static int32_t r183[1024];
static int32_t r184[6144];
static int32_t r185[1024];
static int32_t r186[1024];
static int32_t r187[6144];
static int32_t r188[1];
static int32_t r189[1];
static int32_t r190[1024];
static int32_t r191[1024];
static int32_t r192[1];
static int32_t r193[1024];
static int32_t r194[1024];
static int32_t r195[1024];
static int32_t r196[6144];
static int32_t r197[6144];
static int32_t r198[1024];
static int32_t r199[6144];
static int32_t r200[1024];
static int32_t r201[6144];
static int32_t r202[6144];
static int32_t r203[1024];
static int32_t r204[1024];
static uint8_t r205[1024];
static int32_t r206[1024];
static int32_t r207[1024];
static int32_t r208[1];
static int32_t r209[1024];
static int32_t r210[1024];
static int32_t r211[1024];
static int32_t r212[16384];
static int32_t r213[16384];
static int32_t r214[16384];
static int32_t r215[16000];
static int32_t r216[16000];
static int32_t r217[16000];
static int32_t r218[16000];
static int32_t r219[16000];
static const int32_t *const r220 = rom17_lit;
static const int32_t *const r221 = rom18_lit;
static int32_t r222[1];
static int32_t r223[16000];
static int32_t r224[1];
static int32_t r225[16000];
static int32_t r226[8000];
static int32_t r227[8000];
static int32_t r228[8000];
static int32_t r229[8000];
static int32_t r230[8000];
static int32_t r231[8000];
static int32_t r232[80];
static int32_t r233[80];
static int32_t r234[1];
static int32_t r235[8015];
static int32_t r236[1];
static int32_t r237[8207];
static int32_t r238[1024];
static int32_t r239[1024];
static int32_t r240[16];
static int32_t r241[16];
static int32_t r242[16384];
static int32_t r243[8];
static int32_t r244[8];
static int32_t r245[8207];
static int32_t r246[16384];
static int32_t r247[80];
static int32_t r248[1];
static uint8_t r249[1];
static const int32_t *const r250 = rom19_lit;
static int32_t r251[1];
static int32_t r252[1];
static int32_t r253[1039];
static uint8_t r254[16384];
static int32_t r255[16384];
static int32_t r256[16384];
static int32_t r257[16384];
static int32_t r258[16384];
static int32_t r259[16384];
static int32_t r260[81920];
static int32_t r261[1];
static int32_t r262[81920];
static int32_t r263[1];
static int32_t r264[81920];
static int32_t r265[81920];
static int32_t r266[1];
static int32_t r267[81920];
static int32_t r268[1];
static int32_t r269[81920];
static int32_t r270[81920];
static int32_t r271[5120];
static int32_t r272[5120];
static int32_t r273[81920];
static int32_t r274[1];
static int32_t r275[1];
static int32_t r276[5120];
static int32_t r277[5120];
static int32_t r278[1];
static int32_t r279[5120];
static int32_t r280[5120];
static int32_t r281[5120];
static int32_t r282[81920];
static int32_t r283[81920];
static int32_t r284[5120];
static int32_t r285[81920];
static int32_t r286[5120];
static int32_t r287[81920];
static int32_t r288[81920];
static int32_t r289[5120];
static int32_t r290[5120];
static uint8_t r291[5120];
static int32_t r292[5120];
static int32_t r293[5120];
static int32_t r294[1];
static int32_t r295[5120];
static int32_t r296[5120];
static int32_t r297[81920];
static int32_t r298[5120];
static int32_t r299[5120];
static int32_t r300[81920];
static int32_t r301[1];
static int32_t r302[1];
static int32_t r303[5120];
static int32_t r304[5120];
static int32_t r305[1];
static int32_t r306[5120];
static int32_t r307[5120];
static int32_t r308[5120];
static int32_t r309[81920];
static int32_t r310[81920];
static int32_t r311[5120];
static int32_t r312[81920];
static int32_t r313[5120];
static int32_t r314[81920];
static int32_t r315[81920];
static int32_t r316[5120];
static int32_t r317[5120];
static uint8_t r318[5120];
static int32_t r319[5120];
static int32_t r320[5120];
static int32_t r321[1];
static int32_t r322[5120];
static int32_t r323[5120];
static int32_t r324[5120];
static int32_t r325[40960];
static int32_t r326[40960];
static int32_t r327[40960];
static int32_t r328[40000];
static int32_t r329[40000];
static int32_t r330[40000];
static int32_t r331[5];
static int32_t r332[5];
static int32_t r333[8000];
static int32_t r334[6];
static int32_t r335[6];
static int32_t r336[1];
static int32_t r337[8005];
static int32_t r338[1];
static int32_t r339[8197];
static int32_t r340[1024];
static int32_t r341[1024];
static int32_t r342[6];
static int32_t r343[6];
static int32_t r344[6144];
static int32_t r345[8];
static int32_t r346[8];
static int32_t r347[8197];
static int32_t r348[6144];
static int32_t r349[6];
static int32_t r350[1];
static uint8_t r351[1];
static const int32_t *const r352 = rom20_lit;
static int32_t r353[1];
static int32_t r354[1];
static int32_t r355[1029];
static uint8_t r356[6144];
static int32_t r357[6144];
static int32_t r358[6144];
static int32_t r359[6144];
static int32_t r360[6144];
static int32_t r361[6144];
static int32_t r362[6144];
static int32_t r363[1];
static int32_t r364[6144];
static int32_t r365[1];
static int32_t r366[6144];
static int32_t r367[6144];
static int32_t r368[1];
static int32_t r369[6144];
static int32_t r370[1];
static int32_t r371[6144];
static int32_t r372[6144];
static int32_t r373[1024];
static int32_t r374[1024];
static int32_t r375[6144];
static int32_t r376[1];
static int32_t r377[1];
static int32_t r378[1024];
static int32_t r379[1024];
static int32_t r380[1];
static int32_t r381[1024];
static int32_t r382[1024];
static int32_t r383[1024];
static int32_t r384[6144];
static int32_t r385[6144];
static int32_t r386[1024];
static int32_t r387[6144];
static int32_t r388[1024];
static int32_t r389[6144];
static int32_t r390[6144];
static int32_t r391[1024];
static int32_t r392[1024];
static uint8_t r393[1024];
static int32_t r394[1024];
static int32_t r395[1024];
static int32_t r396[1];
static int32_t r397[1024];
static int32_t r398[1024];
static int32_t r399[6144];
static int32_t r400[1024];
static int32_t r401[1024];
static int32_t r402[6144];
static int32_t r403[1];
static int32_t r404[1];
static int32_t r405[1024];
static int32_t r406[1024];
static int32_t r407[1];
static int32_t r408[1024];
static int32_t r409[1024];
static int32_t r410[1024];
static int32_t r411[6144];
static int32_t r412[6144];
static int32_t r413[1024];
static int32_t r414[6144];
static int32_t r415[1024];
static int32_t r416[6144];
static int32_t r417[6144];
static int32_t r418[1024];
static int32_t r419[1024];
static uint8_t r420[1024];
static int32_t r421[1024];
static int32_t r422[1024];
static int32_t r423[1];
static int32_t r424[1024];
static int32_t r425[1024];
static int32_t r426[1024];
static int32_t r427[8192];
static int32_t r428[8192];
static int32_t r429[8192];
static int32_t r430[8000];
static int32_t r431[8000];
static int32_t r432[8000];
static int32_t r433[8000];
static int32_t r434[8000];
static int32_t r435[1];
static int32_t r436[8000];
static int32_t r437[1];
static int32_t r438[8000];
static int32_t r439[4000];
static int32_t r440[4000];
static int32_t r441[4000];
static int32_t r442[4000];
static int32_t r443[4000];
static int32_t r444[4000];
static int32_t r445[80];
static int32_t r446[80];
static int32_t r447[1];
static int32_t r448[4015];
static int32_t r449[1];
static int32_t r450[4111];
static int32_t r451[1024];
static int32_t r452[1024];
static int32_t r453[16];
static int32_t r454[16];
static int32_t r455[16384];
static int32_t r456[4];
static int32_t r457[4];
static int32_t r458[4111];
static int32_t r459[16384];
static int32_t r460[80];
static int32_t r461[1];
static uint8_t r462[1];
static const int32_t *const r463 = rom21_lit;
static int32_t r464[1];
static int32_t r465[1];
static int32_t r466[1039];
static uint8_t r467[16384];
static int32_t r468[16384];
static int32_t r469[16384];
static int32_t r470[16384];
static int32_t r471[16384];
static int32_t r472[16384];
static int32_t r473[81920];
static int32_t r474[1];
static int32_t r475[81920];
static int32_t r476[1];
static int32_t r477[81920];
static int32_t r478[81920];
static int32_t r479[1];
static int32_t r480[81920];
static int32_t r481[1];
static int32_t r482[81920];
static int32_t r483[81920];
static int32_t r484[5120];
static int32_t r485[5120];
static int32_t r486[81920];
static int32_t r487[1];
static int32_t r488[1];
static int32_t r489[5120];
static int32_t r490[5120];
static int32_t r491[1];
static int32_t r492[5120];
static int32_t r493[5120];
static int32_t r494[5120];
static int32_t r495[81920];
static int32_t r496[81920];
static int32_t r497[5120];
static int32_t r498[81920];
static int32_t r499[5120];
static int32_t r500[81920];
static int32_t r501[81920];
static int32_t r502[5120];
static int32_t r503[5120];
static uint8_t r504[5120];
static int32_t r505[5120];
static int32_t r506[5120];
static int32_t r507[1];
static int32_t r508[5120];
static int32_t r509[5120];
static int32_t r510[81920];
static int32_t r511[5120];
static int32_t r512[5120];
static int32_t r513[81920];
static int32_t r514[1];
static int32_t r515[1];
static int32_t r516[5120];
static int32_t r517[5120];
static int32_t r518[1];
static int32_t r519[5120];
static int32_t r520[5120];
static int32_t r521[5120];
static int32_t r522[81920];
static int32_t r523[81920];
static int32_t r524[5120];
static int32_t r525[81920];
static int32_t r526[5120];
static int32_t r527[81920];
static int32_t r528[81920];
static int32_t r529[5120];
static int32_t r530[5120];
static uint8_t r531[5120];
static int32_t r532[5120];
static int32_t r533[5120];
static int32_t r534[1];
static int32_t r535[5120];
static int32_t r536[5120];
static int32_t r537[5120];
static int32_t r538[20480];
static int32_t r539[20480];
static int32_t r540[20480];
static int32_t r541[20000];
static int32_t r542[20000];
static int32_t r543[20000];
static int32_t r544[5];
static const int32_t *const r545 = rom22_lit;
static int32_t r546[5];
static int32_t r547[4000];
static int32_t r548[6];
static int32_t r549[6];
static int32_t r550[1];
static int32_t r551[4005];
static int32_t r552[1];
static int32_t r553[4101];
static int32_t r554[1024];
static int32_t r555[1024];
static int32_t r556[6];
static int32_t r557[6];
static int32_t r558[6144];
static int32_t r559[4];
static int32_t r560[4];
static int32_t r561[4101];
static int32_t r562[6144];
static int32_t r563[6];
static int32_t r564[1];
static uint8_t r565[1];
static const int32_t *const r566 = rom23_lit;
static int32_t r567[1];
static int32_t r568[1];
static int32_t r569[1029];
static uint8_t r570[6144];
static int32_t r571[6144];
static int32_t r572[6144];
static int32_t r573[6144];
static int32_t r574[6144];
static int32_t r575[6144];
static int32_t r576[6144];
static int32_t r577[1];
static int32_t r578[6144];
static int32_t r579[1];
static int32_t r580[6144];
static int32_t r581[6144];
static int32_t r582[1];
static int32_t r583[6144];
static int32_t r584[1];
static int32_t r585[6144];
static int32_t r586[6144];
static int32_t r587[1024];
static int32_t r588[1024];
static int32_t r589[6144];
static int32_t r590[1];
static int32_t r591[1];
static int32_t r592[1024];
static int32_t r593[1024];
static int32_t r594[1];
static int32_t r595[1024];
static int32_t r596[1024];
static int32_t r597[1024];
static int32_t r598[6144];
static int32_t r599[6144];
static int32_t r600[1024];
static int32_t r601[6144];
static int32_t r602[1024];
static int32_t r603[6144];
static int32_t r604[6144];
static int32_t r605[1024];
static int32_t r606[1024];
static uint8_t r607[1024];
static int32_t r608[1024];
static int32_t r609[1024];
static int32_t r610[1];
static int32_t r611[1024];
static int32_t r612[1024];
static int32_t r613[6144];
static int32_t r614[1024];
static int32_t r615[1024];
static int32_t r616[6144];
static int32_t r617[1];
static int32_t r618[1];
static int32_t r619[1024];
static int32_t r620[1024];
static int32_t r621[1];
static int32_t r622[1024];
static int32_t r623[1024];
static int32_t r624[1024];
static int32_t r625[6144];
static int32_t r626[6144];
static int32_t r627[1024];
static int32_t r628[6144];
static int32_t r629[1024];
static int32_t r630[6144];
static int32_t r631[6144];
static int32_t r632[1024];
static int32_t r633[1024];
static uint8_t r634[1024];
static int32_t r635[1024];
static int32_t r636[1024];
static int32_t r637[1];
static int32_t r638[1024];
static int32_t r639[1024];
static int32_t r640[1024];
static int32_t r641[4096];
static int32_t r642[4096];
static int32_t r643[4096];
static int32_t r644[4000];
static int32_t r645[4000];
static int32_t r646[4000];
static int32_t r647[4000];
static int32_t r648[4000];
static int32_t r649[1];
static int32_t r650[4000];
static int32_t r651[1];
static int32_t r652[4000];
static int32_t r653[2000];
static int32_t r654[2000];
static int32_t r655[2000];
static int32_t r656[2000];
static int32_t r657[2000];
static int32_t r658[2000];
static int32_t r659[80];
static int32_t r660[80];
static int32_t r661[1];
static int32_t r662[2015];
static int32_t r663[1];
static int32_t r664[2063];
static int32_t r665[1024];
static int32_t r666[1024];
static int32_t r667[16];
static int32_t r668[16];
static int32_t r669[16384];
static int32_t r670[2];
static int32_t r671[2];
static int32_t r672[2063];
static int32_t r673[16384];
static int32_t r674[80];
static int32_t r675[1];
static uint8_t r676[1];
static const int32_t *const r677 = rom24_lit;
static int32_t r678[1];
static int32_t r679[1];
static int32_t r680[1039];
static uint8_t r681[16384];
static int32_t r682[16384];
static int32_t r683[16384];
static int32_t r684[16384];
static int32_t r685[16384];
static int32_t r686[16384];
static int32_t r687[81920];
static int32_t r688[1];
static int32_t r689[81920];
static int32_t r690[1];
static int32_t r691[81920];
static int32_t r692[81920];
static int32_t r693[1];
static int32_t r694[81920];
static int32_t r695[1];
static int32_t r696[81920];
static int32_t r697[81920];
static int32_t r698[5120];
static int32_t r699[5120];
static int32_t r700[81920];
static int32_t r701[1];
static int32_t r702[1];
static int32_t r703[5120];
static int32_t r704[5120];
static int32_t r705[1];
static int32_t r706[5120];
static int32_t r707[5120];
static int32_t r708[5120];
static int32_t r709[81920];
static int32_t r710[81920];
static int32_t r711[5120];
static int32_t r712[81920];
static int32_t r713[5120];
static int32_t r714[81920];
static int32_t r715[81920];
static int32_t r716[5120];
static int32_t r717[5120];
static uint8_t r718[5120];
static int32_t r719[5120];
static int32_t r720[5120];
static int32_t r721[1];
static int32_t r722[5120];
static int32_t r723[5120];
static int32_t r724[81920];
static int32_t r725[5120];
static int32_t r726[5120];
static int32_t r727[81920];
static int32_t r728[1];
static int32_t r729[1];
static int32_t r730[5120];
static int32_t r731[5120];
static int32_t r732[1];
static int32_t r733[5120];
static int32_t r734[5120];
static int32_t r735[5120];
static int32_t r736[81920];
static int32_t r737[81920];
static int32_t r738[5120];
static int32_t r739[81920];
static int32_t r740[5120];
static int32_t r741[81920];
static int32_t r742[81920];
static int32_t r743[5120];
static int32_t r744[5120];
static uint8_t r745[5120];
static int32_t r746[5120];
static int32_t r747[5120];
static int32_t r748[1];
static int32_t r749[5120];
static int32_t r750[5120];
static int32_t r751[5120];
static int32_t r752[10240];
static int32_t r753[10240];
static int32_t r754[10240];
static int32_t r755[10000];
static int32_t r756[10000];
static int32_t r757[10000];
static int32_t r758[5];
static const int32_t *const r759 = rom25_lit;
static int32_t r760[5];
static int32_t r761[2000];
static int32_t r762[6];
static int32_t r763[6];
static int32_t r764[1];
static int32_t r765[2005];
static int32_t r766[1];
static int32_t r767[2053];
static int32_t r768[1024];
static int32_t r769[1024];
static int32_t r770[6];
static int32_t r771[6];
static int32_t r772[6144];
static int32_t r773[2];
static int32_t r774[2];
static int32_t r775[2053];
static int32_t r776[6144];
static int32_t r777[6];
static int32_t r778[1];
static uint8_t r779[1];
static const int32_t *const r780 = rom26_lit;
static int32_t r781[1];
static int32_t r782[1];
static int32_t r783[1029];
static uint8_t r784[6144];
static int32_t r785[6144];
static int32_t r786[6144];
static int32_t r787[6144];
static int32_t r788[6144];
static int32_t r789[6144];
static int32_t r790[6144];
static int32_t r791[1];
static int32_t r792[6144];
static int32_t r793[1];
static int32_t r794[6144];
static int32_t r795[6144];
static int32_t r796[1];
static int32_t r797[6144];
static int32_t r798[1];
static int32_t r799[6144];
static int32_t r800[6144];
static int32_t r801[1024];
static int32_t r802[1024];
static int32_t r803[6144];
static int32_t r804[1];
static int32_t r805[1];
static int32_t r806[1024];
static int32_t r807[1024];
static int32_t r808[1];
static int32_t r809[1024];
static int32_t r810[1024];
static int32_t r811[1024];
static int32_t r812[6144];
static int32_t r813[6144];
static int32_t r814[1024];
static int32_t r815[6144];
static int32_t r816[1024];
static int32_t r817[6144];
static int32_t r818[6144];
static int32_t r819[1024];
static int32_t r820[1024];
static uint8_t r821[1024];
static int32_t r822[1024];
static int32_t r823[1024];
static int32_t r824[1];
static int32_t r825[1024];
static int32_t r826[1024];
static int32_t r827[6144];
static int32_t r828[1024];
static int32_t r829[1024];
static int32_t r830[6144];
static int32_t r831[1];
static int32_t r832[1];
static int32_t r833[1024];
static int32_t r834[1024];
static int32_t r835[1];
static int32_t r836[1024];
static int32_t r837[1024];
static int32_t r838[1024];
static int32_t r839[6144];
static int32_t r840[6144];
static int32_t r841[1024];
static int32_t r842[6144];
static int32_t r843[1024];
static int32_t r844[6144];
static int32_t r845[6144];
static int32_t r846[1024];
static int32_t r847[1024];
static uint8_t r848[1024];
static int32_t r849[1024];
static int32_t r850[1024];
static int32_t r851[1];
static int32_t r852[1024];
static int32_t r853[1024];
static int32_t r854[1024];
static int32_t r855[2048];
static int32_t r856[2048];
static int32_t r857[2048];
static int32_t r858[2000];
static int32_t r859[2000];
static int32_t r860[2000];
static int32_t r861[2000];
static int32_t r862[2000];
static int32_t r863[1];
static int32_t r864[2000];
static int32_t r865[1];
static int32_t r866[2000];
static int32_t r867[1000];
static int32_t r868[1000];
static int32_t r869[1000];
static int32_t r870[1000];
static int32_t r871[1000];
static int32_t r872[1000];
static int32_t r873[80];
static int32_t r874[80];
static int32_t r875[1];
static int32_t r876[1015];
static int32_t r877[1000];
static int32_t r878[1000];
static int32_t r879[16];
static int32_t r880[16];
static int32_t r881[16000];
static uint8_t r882[16000];
static const int32_t *const r883 = rom27_lit;
static int32_t r884[16000];
static int32_t r885[16000];
static int32_t r886[16000];
static int32_t r887[16000];
static int32_t r888[16000];
static int32_t r889[80000];
static int32_t r890[1];
static int32_t r891[80000];
static int32_t r892[1];
static int32_t r893[80000];
static int32_t r894[80000];
static int32_t r895[1];
static int32_t r896[80000];
static int32_t r897[1];
static int32_t r898[80000];
static int32_t r899[80000];
static int32_t r900[5000];
static int32_t r901[5000];
static int32_t r902[80000];
static int32_t r903[1];
static int32_t r904[1];
static int32_t r905[5000];
static int32_t r906[5000];
static int32_t r907[1];
static int32_t r908[5000];
static int32_t r909[5000];
static int32_t r910[5000];
static int32_t r911[80000];
static int32_t r912[80000];
static int32_t r913[5000];
static int32_t r914[80000];
static int32_t r915[5000];
static int32_t r916[80000];
static int32_t r917[80000];
static int32_t r918[5000];
static int32_t r919[5000];
static uint8_t r920[5000];
static int32_t r921[5000];
static int32_t r922[5000];
static int32_t r923[1];
static int32_t r924[5000];
static int32_t r925[5000];
static int32_t r926[80000];
static int32_t r927[5000];
static int32_t r928[5000];
static int32_t r929[80000];
static int32_t r930[1];
static int32_t r931[1];
static int32_t r932[5000];
static int32_t r933[5000];
static int32_t r934[1];
static int32_t r935[5000];
static int32_t r936[5000];
static int32_t r937[5000];
static int32_t r938[80000];
static int32_t r939[80000];
static int32_t r940[5000];
static int32_t r941[80000];
static int32_t r942[5000];
static int32_t r943[80000];
static int32_t r944[80000];
static int32_t r945[5000];
static int32_t r946[5000];
static uint8_t r947[5000];
static int32_t r948[5000];
static int32_t r949[5000];
static int32_t r950[1];
static int32_t r951[5000];
static int32_t r952[5000];
static int32_t r953[5000];
static int32_t r954[5000];
static int32_t r955[5000];
static int32_t r956[5];
static const int32_t *const r957 = rom28_lit;
static int32_t r958[5];
static int32_t r959[1000];
static int32_t r960[6];
static int32_t r961[6];
static int32_t r962[1];
static int32_t r963[1005];
static int32_t r964[1000];
static int32_t r965[1000];
static int32_t r966[6];
static int32_t r967[6];
static int32_t r968[6000];
static uint8_t r969[6000];
static const int32_t *const r970 = rom29_lit;
static int32_t r971[6000];
static int32_t r972[6000];
static int32_t r973[6000];
static int32_t r974[6000];
static int32_t r975[6000];
static int32_t r976[6000];
static int32_t r977[1];
static int32_t r978[6000];
static int32_t r979[1];
static int32_t r980[6000];
static int32_t r981[6000];
static int32_t r982[1];
static int32_t r983[6000];
static int32_t r984[1];
static int32_t r985[6000];
static int32_t r986[6000];
static int32_t r987[1000];
static int32_t r988[1000];
static int32_t r989[6000];
static int32_t r990[1];
static int32_t r991[1];
static int32_t r992[1000];
static int32_t r993[1000];
static int32_t r994[1];
static int32_t r995[1000];
static int32_t r996[1000];
static int32_t r997[1000];
static int32_t r998[6000];
static int32_t r999[6000];
static int32_t r1000[1000];
static int32_t r1001[6000];
static int32_t r1002[1000];
static int32_t r1003[6000];
static int32_t r1004[6000];
static int32_t r1005[1000];
static int32_t r1006[1000];
static uint8_t r1007[1000];
static int32_t r1008[1000];
static int32_t r1009[1000];
static int32_t r1010[1];
static int32_t r1011[1000];
static int32_t r1012[1000];
static int32_t r1013[6000];
static int32_t r1014[1000];
static int32_t r1015[1000];
static int32_t r1016[6000];
static int32_t r1017[1];
static int32_t r1018[1];
static int32_t r1019[1000];
static int32_t r1020[1000];
static int32_t r1021[1];
static int32_t r1022[1000];
static int32_t r1023[1000];
static int32_t r1024[1000];
static int32_t r1025[6000];
static int32_t r1026[6000];
static int32_t r1027[1000];
static int32_t r1028[6000];
static int32_t r1029[1000];
static int32_t r1030[6000];
static int32_t r1031[6000];
static int32_t r1032[1000];
static int32_t r1033[1000];
static uint8_t r1034[1000];
static int32_t r1035[1000];
static int32_t r1036[1000];
static int32_t r1037[1];
static int32_t r1038[1000];
static int32_t r1039[1000];
static int32_t r1040[1000];
static int32_t r1041[1000];
static int32_t r1042[1000];
static int32_t r1043[1000];
static int32_t r1044[1000];
static int32_t r1045[1];
static int32_t r1046[1000];
static int32_t r1047[1];
static int32_t r1048[1000];
static int32_t r1049[500];
static int32_t r1050[500];
static int32_t r1051[500];
static int32_t r1052[500];
static int32_t r1053[500];
static int32_t r1054[500];
static int32_t r1055[80];
static int32_t r1056[80];
static int32_t r1057[1];
static int32_t r1058[515];
static int32_t r1059[500];
static int32_t r1060[500];
static int32_t r1061[16];
static int32_t r1062[16];
static int32_t r1063[8000];
static uint8_t r1064[8000];
static const int32_t *const r1065 = rom30_lit;
static int32_t r1066[8000];
static int32_t r1067[8000];
static int32_t r1068[8000];
static int32_t r1069[8000];
static int32_t r1070[8000];
static int32_t r1071[40000];
static int32_t r1072[1];
static int32_t r1073[40000];
static int32_t r1074[1];
static int32_t r1075[40000];
static int32_t r1076[40000];
static int32_t r1077[1];
static int32_t r1078[40000];
static int32_t r1079[1];
static int32_t r1080[40000];
static int32_t r1081[40000];
static int32_t r1082[2500];
static int32_t r1083[2500];
static int32_t r1084[40000];
static int32_t r1085[1];
static int32_t r1086[1];
static int32_t r1087[2500];
static int32_t r1088[2500];
static int32_t r1089[1];
static int32_t r1090[2500];
static int32_t r1091[2500];
static int32_t r1092[2500];
static int32_t r1093[40000];
static int32_t r1094[40000];
static int32_t r1095[2500];
static int32_t r1096[40000];
static int32_t r1097[2500];
static int32_t r1098[40000];
static int32_t r1099[40000];
static int32_t r1100[2500];
static int32_t r1101[2500];
static uint8_t r1102[2500];
static int32_t r1103[2500];
static int32_t r1104[2500];
static int32_t r1105[1];
static int32_t r1106[2500];
static int32_t r1107[2500];
static int32_t r1108[40000];
static int32_t r1109[2500];
static int32_t r1110[2500];
static int32_t r1111[40000];
static int32_t r1112[1];
static int32_t r1113[1];
static int32_t r1114[2500];
static int32_t r1115[2500];
static int32_t r1116[1];
static int32_t r1117[2500];
static int32_t r1118[2500];
static int32_t r1119[2500];
static int32_t r1120[40000];
static int32_t r1121[40000];
static int32_t r1122[2500];
static int32_t r1123[40000];
static int32_t r1124[2500];
static int32_t r1125[40000];
static int32_t r1126[40000];
static int32_t r1127[2500];
static int32_t r1128[2500];
static uint8_t r1129[2500];
static int32_t r1130[2500];
static int32_t r1131[2500];
static int32_t r1132[1];
static int32_t r1133[2500];
static int32_t r1134[2500];
static int32_t r1135[2500];
static int32_t r1136[2500];
static int32_t r1137[2500];
static int32_t r1138[5];
static const int32_t *const r1139 = rom31_lit;
static int32_t r1140[5];
static int32_t r1141[30];
static int32_t r1142[30];
static int32_t r1143[30];
static uint8_t r1144[30];
static int32_t r1145[30];
static int32_t r1146[30];
static int32_t r1147[30];
static int32_t r1148[30];
static int32_t r1149[30];
static int32_t r1150[30];
static int32_t r1151[30];
static uint8_t r1152[30];
static int32_t r1153[30];
static uint8_t r1154[30];
static int32_t r1155[30];
static int32_t r1156[30];
static int32_t r1157[30];
static int32_t r1158[30];
static int32_t r1159[30];
static int32_t r1160[30];
static int32_t r1161[30];
static uint8_t r1162[30];
static int32_t r1163[30];
static uint8_t r1164[30];
static int32_t r1165[30];
static uint8_t r1166[30];
static int32_t r1167[30];
static uint8_t r1168[30];
static int32_t r1169[30];
static uint8_t r1170[30];
static int32_t r1171[30];
static int32_t r1172[1];
static int32_t r1173[30];
static int32_t r1174[1];
static int32_t r1175[30];
static int32_t r1176[30];
static int32_t r1177[30];
static int32_t r1178[30];
static int32_t r1179[30];
static int32_t r1180[300];
static int32_t r1181[300];
static int32_t r1182[1];
static int32_t r1183[300];
static int32_t r1184[1];
static int32_t r1185[300];
static int32_t r1186[300];
static int32_t r1187[300];
static int32_t r1188[1];
static int32_t r1189[300];
static int32_t r1190[1];
static int32_t r1191[300];
static int32_t r1192[600];
static int32_t r1193[10];
static int32_t r1194[610];
static int32_t r1195[610];
static int32_t r1196[10];
static const int32_t *const r1197 = rom32_lit;
static int32_t r1198[10];
static int32_t r1199[610];
static int32_t r1200[1];
static int32_t r1201[1];
static int32_t r1202[10];
static int32_t r1203[10];
static int32_t r1204[1];
static int32_t r1205[10];
static int32_t r1206[10];
static int32_t r1207[10];
static int32_t r1208[610];
static int32_t r1209[610];
static int32_t r1210[10];
static uint8_t r1211[10];
static int32_t r1212[10];
static int32_t r1213[10];
static int32_t r1214[1];
static int32_t r1215[10];
static int32_t r1216[10];
static int32_t r1217[300];
static int32_t r1218[300];
static int32_t r1219[1];
static int32_t r1220[300];
static int32_t r1221[1];
static int32_t r1222[300];
static int32_t r1223[300];
static int32_t r1224[300];
static int32_t r1225[1];
static int32_t r1226[300];
static int32_t r1227[1];
static int32_t r1228[300];
static int32_t r1229[600];
static int32_t r1230[10];
static int32_t r1231[610];
static int32_t r1232[610];
static int32_t r1233[10];
static int32_t r1234[10];
static int32_t r1235[610];
static int32_t r1236[1];
static int32_t r1237[1];
static int32_t r1238[10];
static int32_t r1239[10];
static int32_t r1240[1];
static int32_t r1241[10];
static int32_t r1242[10];
static int32_t r1243[10];
static int32_t r1244[610];
static int32_t r1245[610];
static int32_t r1246[10];
static uint8_t r1247[10];
static int32_t r1248[10];
static int32_t r1249[10];
static int32_t r1250[1];
static int32_t r1251[10];
static int32_t r1252[10];
static int32_t r1253[10];
static int32_t r1254[10];
static int32_t r1255[20];
static int32_t r1256[10];
static const int32_t *const r1257 = rom33_lit;
static int32_t r1258[10];
static int32_t r1259[20];
static int32_t r1260[1];
static int32_t r1261[1];
static int32_t r1262[10];
static int32_t r1263[10];
static int32_t r1264[1];
static int32_t r1265[10];
static int32_t r1266[10];
static int32_t r1267[10];
static int32_t r1268[20];
static int32_t r1269[20];
static int32_t r1270[10];
static uint8_t r1271[10];
static int32_t r1272[10];
static int32_t r1273[10];
static int32_t r1274[1];
static int32_t r1275[10];
static int32_t r1276[10];
static int32_t r1277[10];
static int32_t r1278[10];
static int32_t r1279[10];
static int32_t r1280[10];
static int32_t r1281[10];

static void program_run(void) {
    /* shl [shift_left] -> r10 */
    for (long i1 = 0; i1 < 16000; ++i1) {
        r10[i1] = shl32(r0[i1], 1);
    }
    /* rev [rev] -> r11 */
    for (long i2 = 0; i2 < 80; ++i2) {
        long t4 = i2;
        long c30 = t4 / 16; t4 %= 16;
        long c31 = t4;
        r11[i2] = r1[c30 * 16 + (16 - 1 - c31) * 1];
    }
    /* reshape [reshape] -> r12 */
    memcpy(r12, r11, sizeof(int32_t) * 80);
    /* convert [convert_element_type] -> r14 */
    for (long i5 = 0; i5 < 1; ++i5) {
        r14[i5] = (int32_t)r13[0];
    }
    /* pad [pad] -> r15 */
    for (long i6 = 0; i6 < 16015; ++i6) {
        r15[i6] = r14[0];
    }
    for (long i7 = 0; i7 < 16000; ++i7) {
        long t9 = i7;
        long c80 = t9 / 16000; t9 %= 16000;
        long c81 = t9;
        long d10 = 0 + c80 * 1;
        long d11 = 15 + c81 * 1;
        if (d10 >= 0 && d10 < 1 && d11 >= 0 && d11 < 16015) r15[d10 * 16015 + d11 * 1] = r10[i7];
    }
    /* convert [convert_element_type] -> r16 */
    for (long i12 = 0; i12 < 1; ++i12) {
        r16[i12] = (int32_t)r13[0];
    }
    /* pad [pad] -> r17 */
    for (long i13 = 0; i13 < 16399; ++i13) {
        r17[i13] = r16[0];
    }
    for (long i14 = 0; i14 < 16015; ++i14) {
        long t16 = i14;
        long c150 = t16 / 16015; t16 %= 16015;
        long c151 = t16;
        long d17 = 0 + c150 * 1;
        long d18 = 0 + c151 * 1;
        if (d17 >= 0 && d17 < 1 && d18 >= 0 && d18 < 16399) r17[d17 * 16399 + d18 * 1] = r15[i14];
    }
    /* iota [iota] -> r18 */
    for (long i19 = 0; i19 < 1024; ++i19) {
        long t21 = i19;
        long c200 = t21;
        r18[i19] = (int32_t)c200;
    }
    /* broadcast [broadcast_in_dim] -> r19 */
    for (long i22 = 0; i22 < 1024; ++i22) {
        long t24 = i22;
        long c230 = t24 / 1; t24 %= 1;
        long c231 = t24;
        r19[i22] = r18[c230 * 1];
    }
    /* iota [iota] -> r20 */
    for (long i25 = 0; i25 < 16; ++i25) {
        long t27 = i25;
        long c260 = t27;
        r20[i25] = (int32_t)c260;
    }
    /* broadcast [broadcast_in_dim] -> r21 */
    for (long i28 = 0; i28 < 16; ++i28) {
        long t30 = i28;
        long c290 = t30 / 16; t30 %= 16;
        long c291 = t30;
        r21[i28] = r20[c291 * 1];
    }
    /* add [add] -> r22 */
    for (long i31 = 0; i31 < 16384; ++i31) {
        long t33 = i31;
        long c320 = t33 / 16; t33 %= 16;
        long c321 = t33;
        r22[i31] = add32(r19[c320 * 1], r21[c321 * 1]);
    }
    /* iota [iota] -> r23 */
    for (long i34 = 0; i34 < 16; ++i34) {
        long t36 = i34;
        long c350 = t36;
        r23[i34] = (int32_t)c350;
    }
    /* shl [mul] -> r24 */
    for (long i37 = 0; i37 < 16; ++i37) {
        r24[i37] = shl32(r23[i37], 10);
    }
    /* loop [scan] -> r109 */
    memcpy(r25, r17, sizeof(int32_t) * 16399);
    memcpy(r26, r22, sizeof(int32_t) * 16384);
    memcpy(r27, r12, sizeof(int32_t) * 80);
    for (long t38 = 0; t38 < 16; ++t38) {
        memcpy(r28, r24 + t38 * 1, sizeof(int32_t) * 1);
        /* lt [lt] -> r29 */
        for (long i1039 = 0; i1039 < 1; ++i1039) {
            r29[i1039] = r28[0] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r31 */
        for (long i1040 = 0; i1040 < 1; ++i1040) {
            r31[i1040] = add32(r28[0], r30[0]);
        }
        /* select_n [select_n] -> r32 */
        for (long i1041 = 0; i1041 < 1; ++i1041) {
            r32[i1041] = r29[0] == 0 ? r28[0] : (r31[0]);
        }
        /* dynamic_slice [dynamic_slice] -> r33 */
        long s1042 = clamp_start((long)r13[0], 1, 1);
        long s1043 = clamp_start((long)r32[0], 16399, 1039);
        {
        for (long i1044 = 0; i1044 < 1039; ++i1044) {
            long t1046 = i1044;
            long c10450 = t1046 / 1039; t1046 %= 1039;
            long c10451 = t1046;
            r33[i1044] = r25[(s1042 + c10450) * 16399 + (s1043 + c10451) * 1];
        }
        }
        /* lt [lt] -> r34 */
        for (long i1047 = 0; i1047 < 16384; ++i1047) {
            r34[i1047] = r26[i1047] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r36 */
        for (long i1048 = 0; i1048 < 16384; ++i1048) {
            r36[i1048] = add32(r26[i1048], r35[0]);
        }
        /* select_n [select_n] -> r37 */
        for (long i1049 = 0; i1049 < 16384; ++i1049) {
            r37[i1049] = r34[i1049] == 0 ? r26[i1049] : (r36[i1049]);
        }
        /* broadcast [broadcast_in_dim] -> r38 */
        for (long i1050 = 0; i1050 < 16384; ++i1050) {
            long t1052 = i1050;
            long c10510 = t1052 / 16; t1052 %= 16;
            long c10511 = t1052 / 1; t1052 %= 1;
            long c10512 = t1052;
            r38[i1050] = r37[c10510 * 16 + c10511 * 1];
        }
        /* gather [gather] -> r39 */
        for (long i1053 = 0; i1053 < 16384; ++i1053) {
            long t1055 = i1053;
            long c10540 = t1055 / 16384; t1055 %= 16384;
            long c10541 = t1055 / 16; t1055 %= 16;
            long c10542 = t1055;
            long row1056 = c10541 * 16 + c10542 * 1;
            long s1057 = clamp_start((long)r38[row1056 + 0], 1039, 1);
            r39[i1053] = r33[c10540 * 1039 + s1057 * 1];
        }
        /* broadcast [broadcast_in_dim] -> r40 */
        for (long i1058 = 0; i1058 < 16384; ++i1058) {
            long t1060 = i1058;
            long c10590 = t1060 / 16384; t1060 %= 16384;
            long c10591 = t1060 / 16384; t1060 %= 16384;
            long c10592 = t1060 / 16; t1060 %= 16;
            long c10593 = t1060;
            r40[i1058] = r39[c10592 * 16 + c10593 * 1];
        }
        /* add [add] -> r41 */
        for (long i1061 = 0; i1061 < 81920; ++i1061) {
            long t1063 = i1061;
            long c10620 = t1063 / 16384; t1063 %= 16384;
            long c10621 = t1063 / 16384; t1063 %= 16384;
            long c10622 = t1063 / 16; t1063 %= 16;
            long c10623 = t1063;
            r41[i1061] = add32(r27[c10620 * 16 + c10623 * 1], r40[c10622 * 16 + c10623 * 1]);
        }
        /* convert [convert_element_type] -> r44 */
        for (long i1064 = 0; i1064 < 1; ++i1064) {
            r44[i1064] = (int32_t)r42[0];
        }
        /* max [max] -> r45 */
        for (long i1065 = 0; i1065 < 81920; ++i1065) {
            r45[i1065] = max32(r44[0], r41[i1065]);
        }
        /* convert [convert_element_type] -> r46 */
        for (long i1066 = 0; i1066 < 1; ++i1066) {
            r46[i1066] = (int32_t)r43[0];
        }
        /* min [min] -> r47 */
        for (long i1067 = 0; i1067 < 81920; ++i1067) {
            r47[i1067] = min32(r46[0], r45[i1067]);
        }
        /* sub [sub] -> r48 */
        for (long i1068 = 0; i1068 < 81920; ++i1068) {
            long t1070 = i1068;
            long c10690 = t1070 / 16384; t1070 %= 16384;
            long c10691 = t1070 / 16384; t1070 %= 16384;
            long c10692 = t1070 / 16; t1070 %= 16;
            long c10693 = t1070;
            r48[i1068] = sub32(r27[c10690 * 16 + c10693 * 1], r40[c10692 * 16 + c10693 * 1]);
        }
        /* convert [convert_element_type] -> r49 */
        for (long i1071 = 0; i1071 < 1; ++i1071) {
            r49[i1071] = (int32_t)r42[0];
        }
        /* max [max] -> r50 */
        for (long i1072 = 0; i1072 < 81920; ++i1072) {
            r50[i1072] = max32(r49[0], r48[i1072]);
        }
        /* convert [convert_element_type] -> r51 */
        for (long i1073 = 0; i1073 < 1; ++i1073) {
            r51[i1073] = (int32_t)r43[0];
        }
        /* min [min] -> r52 */
        for (long i1074 = 0; i1074 < 81920; ++i1074) {
            r52[i1074] = min32(r51[0], r50[i1074]);
        }
        /* abs [abs] -> r53 */
        for (long i1075 = 0; i1075 < 81920; ++i1075) {
            r53[i1075] = abs32(r47[i1075]);
        }
        /* reduce_max [reduce_max] -> r54 */
        for (long i1076 = 0; i1076 < 5120; ++i1076) {
            r54[i1076] = (-2147483647 - 1);
        }
        for (long i1077 = 0; i1077 < 81920; ++i1077) {
            long t1079 = i1077;
            long c10780 = t1079 / 16384; t1079 %= 16384;
            long c10781 = t1079 / 16384; t1079 %= 16384;
            long c10782 = t1079 / 16; t1079 %= 16;
            long c10783 = t1079;
            r54[c10780 * 1024 + c10781 * 1024 + c10782 * 1] = max32(r54[c10780 * 1024 + c10781 * 1024 + c10782 * 1], r53[i1077]);
        }
        /* sub [sub] -> r56 */
        for (long i1080 = 0; i1080 < 5120; ++i1080) {
            r56[i1080] = sub32(r54[i1080], r55[0]);
        }
        /* loop [scan] -> r78 */
        memcpy(r57, r47, sizeof(int32_t) * 81920);
        memcpy(r58, r55, sizeof(int32_t) * 1);
        memcpy(r59, r13, sizeof(int32_t) * 1);
        memcpy(r60, r56, sizeof(int32_t) * 5120);
        memcpy(r61, r54, sizeof(int32_t) * 5120);
        for (long t1081 = 0; t1081 < 12; ++t1081) {
            /* add [add] -> r62 */
            for (long i2082 = 0; i2082 < 1; ++i2082) {
                r62[i2082] = add32(r59[0], r9[0]);
            }
            /* add [add] -> r63 */
            for (long i2083 = 0; i2083 < 5120; ++i2083) {
                r63[i2083] = add32(r60[i2083], r61[i2083]);
            }
            /* shra [shift_right_arithmetic] -> r64 */
            for (long i2084 = 0; i2084 < 5120; ++i2084) {
                r64[i2084] = asr32(r63[i2084], 1);
            }
            /* broadcast [broadcast_in_dim] -> r65 */
            for (long i2085 = 0; i2085 < 5120; ++i2085) {
                long t2087 = i2085;
                long c20860 = t2087 / 1024; t2087 %= 1024;
                long c20861 = t2087 / 1024; t2087 %= 1024;
                long c20862 = t2087 / 1; t2087 %= 1;
                long c20863 = t2087;
                r65[i2085] = r64[c20860 * 1024 + c20862 * 1];
            }
            /* sub [sub] -> r66 */
            for (long i2088 = 0; i2088 < 81920; ++i2088) {
                long t2090 = i2088;
                long c20890 = t2090 / 16384; t2090 %= 16384;
                long c20891 = t2090 / 16384; t2090 %= 16384;
                long c20892 = t2090 / 16; t2090 %= 16;
                long c20893 = t2090;
                r66[i2088] = sub32(r57[c20890 * 16384 + c20892 * 16 + c20893 * 1], r65[c20890 * 1024 + c20892 * 1]);
            }
            /* max [max] -> r67 */
            for (long i2091 = 0; i2091 < 81920; ++i2091) {
                r67[i2091] = max32(r66[i2091], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r68 */
            for (long i2092 = 0; i2092 < 5120; ++i2092) {
                r68[i2092] = 0;
            }
            for (long i2093 = 0; i2093 < 81920; ++i2093) {
                long t2095 = i2093;
                long c20940 = t2095 / 16384; t2095 %= 16384;
                long c20941 = t2095 / 16384; t2095 %= 16384;
                long c20942 = t2095 / 16; t2095 %= 16;
                long c20943 = t2095;
                r68[c20940 * 1024 + c20941 * 1024 + c20942 * 1] = add32(r68[c20940 * 1024 + c20941 * 1024 + c20942 * 1], r67[i2093]);
            }
            /* neg [neg] -> r69 */
            for (long i2096 = 0; i2096 < 81920; ++i2096) {
                r69[i2096] = neg32(r57[i2096]);
            }
            /* broadcast [broadcast_in_dim] -> r70 */
            for (long i2097 = 0; i2097 < 5120; ++i2097) {
                long t2099 = i2097;
                long c20980 = t2099 / 1024; t2099 %= 1024;
                long c20981 = t2099 / 1024; t2099 %= 1024;
                long c20982 = t2099 / 1; t2099 %= 1;
                long c20983 = t2099;
                r70[i2097] = r64[c20980 * 1024 + c20982 * 1];
            }
            /* sub [sub] -> r71 */
            for (long i2100 = 0; i2100 < 81920; ++i2100) {
                long t2102 = i2100;
                long c21010 = t2102 / 16384; t2102 %= 16384;
                long c21011 = t2102 / 16384; t2102 %= 16384;
                long c21012 = t2102 / 16; t2102 %= 16;
                long c21013 = t2102;
                r71[i2100] = sub32(r69[c21010 * 16384 + c21012 * 16 + c21013 * 1], r70[c21010 * 1024 + c21012 * 1]);
            }
            /* max [max] -> r72 */
            for (long i2103 = 0; i2103 < 81920; ++i2103) {
                r72[i2103] = max32(r71[i2103], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r73 */
            for (long i2104 = 0; i2104 < 5120; ++i2104) {
                r73[i2104] = 0;
            }
            for (long i2105 = 0; i2105 < 81920; ++i2105) {
                long t2107 = i2105;
                long c21060 = t2107 / 16384; t2107 %= 16384;
                long c21061 = t2107 / 16384; t2107 %= 16384;
                long c21062 = t2107 / 16; t2107 %= 16;
                long c21063 = t2107;
                r73[c21060 * 1024 + c21061 * 1024 + c21062 * 1] = add32(r73[c21060 * 1024 + c21061 * 1024 + c21062 * 1], r72[i2105]);
            }
            /* add [add] -> r74 */
            for (long i2108 = 0; i2108 < 5120; ++i2108) {
                r74[i2108] = add32(r68[i2108], r73[i2108]);
            }
            /* gt [gt] -> r75 */
            for (long i2109 = 0; i2109 < 5120; ++i2109) {
                r75[i2109] = r74[i2109] > r58[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r76 */
            for (long i2110 = 0; i2110 < 5120; ++i2110) {
                r76[i2110] = r75[i2110] == 0 ? r60[i2110] : (r64[i2110]);
            }
            /* select_n [select_n] -> r77 */
            for (long i2111 = 0; i2111 < 5120; ++i2111) {
                r77[i2111] = r75[i2111] == 0 ? r64[i2111] : (r61[i2111]);
            }
            memcpy(r59, r62, sizeof(int32_t) * 1);
            memcpy(r60, r76, sizeof(int32_t) * 5120);
            memcpy(r61, r77, sizeof(int32_t) * 5120);
        }
        memcpy(r78, r59, sizeof(int32_t) * 1);
        memcpy(r79, r60, sizeof(int32_t) * 5120);
        memcpy(r80, r61, sizeof(int32_t) * 5120);
        /* abs [abs] -> r81 */
        for (long i2112 = 0; i2112 < 81920; ++i2112) {
            r81[i2112] = abs32(r52[i2112]);
        }
        /* reduce_max [reduce_max] -> r82 */
        for (long i2113 = 0; i2113 < 5120; ++i2113) {
            r82[i2113] = (-2147483647 - 1);
        }
        for (long i2114 = 0; i2114 < 81920; ++i2114) {
            long t2116 = i2114;
            long c21150 = t2116 / 16384; t2116 %= 16384;
            long c21151 = t2116 / 16384; t2116 %= 16384;
            long c21152 = t2116 / 16; t2116 %= 16;
            long c21153 = t2116;
            r82[c21150 * 1024 + c21151 * 1024 + c21152 * 1] = max32(r82[c21150 * 1024 + c21151 * 1024 + c21152 * 1], r81[i2114]);
        }
        /* sub [sub] -> r83 */
        for (long i2117 = 0; i2117 < 5120; ++i2117) {
            r83[i2117] = sub32(r82[i2117], r55[0]);
        }
        /* loop [scan] -> r105 */
        memcpy(r84, r52, sizeof(int32_t) * 81920);
        memcpy(r85, r55, sizeof(int32_t) * 1);
        memcpy(r86, r13, sizeof(int32_t) * 1);
        memcpy(r87, r83, sizeof(int32_t) * 5120);
        memcpy(r88, r82, sizeof(int32_t) * 5120);
        for (long t2118 = 0; t2118 < 12; ++t2118) {
            /* add [add] -> r89 */
            for (long i3119 = 0; i3119 < 1; ++i3119) {
                r89[i3119] = add32(r86[0], r9[0]);
            }
            /* add [add] -> r90 */
            for (long i3120 = 0; i3120 < 5120; ++i3120) {
                r90[i3120] = add32(r87[i3120], r88[i3120]);
            }
            /* shra [shift_right_arithmetic] -> r91 */
            for (long i3121 = 0; i3121 < 5120; ++i3121) {
                r91[i3121] = asr32(r90[i3121], 1);
            }
            /* broadcast [broadcast_in_dim] -> r92 */
            for (long i3122 = 0; i3122 < 5120; ++i3122) {
                long t3124 = i3122;
                long c31230 = t3124 / 1024; t3124 %= 1024;
                long c31231 = t3124 / 1024; t3124 %= 1024;
                long c31232 = t3124 / 1; t3124 %= 1;
                long c31233 = t3124;
                r92[i3122] = r91[c31230 * 1024 + c31232 * 1];
            }
            /* sub [sub] -> r93 */
            for (long i3125 = 0; i3125 < 81920; ++i3125) {
                long t3127 = i3125;
                long c31260 = t3127 / 16384; t3127 %= 16384;
                long c31261 = t3127 / 16384; t3127 %= 16384;
                long c31262 = t3127 / 16; t3127 %= 16;
                long c31263 = t3127;
                r93[i3125] = sub32(r84[c31260 * 16384 + c31262 * 16 + c31263 * 1], r92[c31260 * 1024 + c31262 * 1]);
            }
            /* max [max] -> r94 */
            for (long i3128 = 0; i3128 < 81920; ++i3128) {
                r94[i3128] = max32(r93[i3128], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r95 */
            for (long i3129 = 0; i3129 < 5120; ++i3129) {
                r95[i3129] = 0;
            }
            for (long i3130 = 0; i3130 < 81920; ++i3130) {
                long t3132 = i3130;
                long c31310 = t3132 / 16384; t3132 %= 16384;
                long c31311 = t3132 / 16384; t3132 %= 16384;
                long c31312 = t3132 / 16; t3132 %= 16;
                long c31313 = t3132;
                r95[c31310 * 1024 + c31311 * 1024 + c31312 * 1] = add32(r95[c31310 * 1024 + c31311 * 1024 + c31312 * 1], r94[i3130]);
            }
            /* neg [neg] -> r96 */
            for (long i3133 = 0; i3133 < 81920; ++i3133) {
                r96[i3133] = neg32(r84[i3133]);
            }
            /* broadcast [broadcast_in_dim] -> r97 */
            for (long i3134 = 0; i3134 < 5120; ++i3134) {
                long t3136 = i3134;
                long c31350 = t3136 / 1024; t3136 %= 1024;
                long c31351 = t3136 / 1024; t3136 %= 1024;
                long c31352 = t3136 / 1; t3136 %= 1;
                long c31353 = t3136;
                r97[i3134] = r91[c31350 * 1024 + c31352 * 1];
            }
            /* sub [sub] -> r98 */
            for (long i3137 = 0; i3137 < 81920; ++i3137) {
                long t3139 = i3137;
                long c31380 = t3139 / 16384; t3139 %= 16384;
                long c31381 = t3139 / 16384; t3139 %= 16384;
                long c31382 = t3139 / 16; t3139 %= 16;
                long c31383 = t3139;
                r98[i3137] = sub32(r96[c31380 * 16384 + c31382 * 16 + c31383 * 1], r97[c31380 * 1024 + c31382 * 1]);
            }
            /* max [max] -> r99 */
            for (long i3140 = 0; i3140 < 81920; ++i3140) {
                r99[i3140] = max32(r98[i3140], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r100 */
            for (long i3141 = 0; i3141 < 5120; ++i3141) {
                r100[i3141] = 0;
            }
            for (long i3142 = 0; i3142 < 81920; ++i3142) {
                long t3144 = i3142;
                long c31430 = t3144 / 16384; t3144 %= 16384;
                long c31431 = t3144 / 16384; t3144 %= 16384;
                long c31432 = t3144 / 16; t3144 %= 16;
                long c31433 = t3144;
                r100[c31430 * 1024 + c31431 * 1024 + c31432 * 1] = add32(r100[c31430 * 1024 + c31431 * 1024 + c31432 * 1], r99[i3142]);
            }
            /* add [add] -> r101 */
            for (long i3145 = 0; i3145 < 5120; ++i3145) {
                r101[i3145] = add32(r95[i3145], r100[i3145]);
            }
            /* gt [gt] -> r102 */
            for (long i3146 = 0; i3146 < 5120; ++i3146) {
                r102[i3146] = r101[i3146] > r85[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r103 */
            for (long i3147 = 0; i3147 < 5120; ++i3147) {
                r103[i3147] = r102[i3147] == 0 ? r87[i3147] : (r91[i3147]);
            }
            /* select_n [select_n] -> r104 */
            for (long i3148 = 0; i3148 < 5120; ++i3148) {
                r104[i3148] = r102[i3148] == 0 ? r91[i3148] : (r88[i3148]);
            }
            memcpy(r86, r89, sizeof(int32_t) * 1);
            memcpy(r87, r103, sizeof(int32_t) * 5120);
            memcpy(r88, r104, sizeof(int32_t) * 5120);
        }
        memcpy(r105, r86, sizeof(int32_t) * 1);
        memcpy(r106, r87, sizeof(int32_t) * 5120);
        memcpy(r107, r88, sizeof(int32_t) * 5120);
        /* sub [sub] -> r108 */
        for (long i3149 = 0; i3149 < 5120; ++i3149) {
            r108[i3149] = sub32(r80[i3149], r107[i3149]);
        }
        memcpy(r109 + t38 * 5120, r108, sizeof(int32_t) * 5120);
    }
    /* transpose [transpose] -> r110 */
    for (long i3150 = 0; i3150 < 81920; ++i3150) {
        long t3152 = i3150;
        long c31510 = t3152 / 16384; t3152 %= 16384;
        long c31511 = t3152 / 16384; t3152 %= 16384;
        long c31512 = t3152 / 1024; t3152 %= 1024;
        long c31513 = t3152;
        r110[i3150] = r109[c31510 * 1024 + c31511 * 1024 + c31512 * 5120 + c31513 * 1];
    }
    /* reshape [reshape] -> r111 */
    memcpy(r111, r110, sizeof(int32_t) * 81920);
    /* slice [slice] -> r112 */
    for (long i3153 = 0; i3153 < 80000; ++i3153) {
        long t3155 = i3153;
        long c31540 = t3155 / 16000; t3155 %= 16000;
        long c31541 = t3155 / 16000; t3155 %= 16000;
        long c31542 = t3155;
        r112[i3153] = r111[(0 + c31540 * 1) * 16384 + (0 + c31541 * 1) * 16384 + (0 + c31542 * 1) * 1];
    }
    /* transpose [transpose] -> r113 */
    for (long i3156 = 0; i3156 < 80000; ++i3156) {
        long t3158 = i3156;
        long c31570 = t3158 / 80000; t3158 %= 80000;
        long c31571 = t3158 / 16000; t3158 %= 16000;
        long c31572 = t3158;
        r113[i3156] = r112[c31570 * 16000 + c31571 * 16000 + c31572 * 1];
    }
    /* max [max] -> r114 */
    for (long i3159 = 0; i3159 < 80000; ++i3159) {
        r114[i3159] = max32(r113[i3159], r13[0]);
    }
    /* reduce_sum [reduce_sum] -> r115 */
    for (long i3160 = 0; i3160 < 5; ++i3160) {
        r115[i3160] = 0;
    }
    for (long i3161 = 0; i3161 < 80000; ++i3161) {
        long t3163 = i3161;
        long c31620 = t3163 / 80000; t3163 %= 80000;
        long c31621 = t3163 / 16000; t3163 %= 16000;
        long c31622 = t3163;
        r115[c31620 * 5 + c31621 * 1] = add32(r115[c31620 * 5 + c31621 * 1], r114[i3161]);
    }
    /* shl [shift_left] -> r116 */
    for (long i3164 = 0; i3164 < 5; ++i3164) {
        r116[i3164] = shl32(r115[i3164], 0);
    }
    /* shl [shift_left] -> r117 */
    for (long i3165 = 0; i3165 < 16000; ++i3165) {
        r117[i3165] = shl32(r0[i3165], 1);
    }
    /* rev [rev] -> r118 */
    for (long i3166 = 0; i3166 < 6; ++i3166) {
        long t3168 = i3166;
        long c31670 = t3168 / 6; t3168 %= 6;
        long c31671 = t3168;
        r118[i3166] = r2[c31670 * 6 + (6 - 1 - c31671) * 1];
    }
    /* reshape [reshape] -> r119 */
    memcpy(r119, r118, sizeof(int32_t) * 6);
    /* convert [convert_element_type] -> r120 */
    for (long i3169 = 0; i3169 < 1; ++i3169) {
        r120[i3169] = (int32_t)r13[0];
    }
    /* pad [pad] -> r121 */
    for (long i3170 = 0; i3170 < 16005; ++i3170) {
        r121[i3170] = r120[0];
    }
    for (long i3171 = 0; i3171 < 16000; ++i3171) {
        long t3173 = i3171;
        long c31720 = t3173 / 16000; t3173 %= 16000;
        long c31721 = t3173;
        long d3174 = 0 + c31720 * 1;
        long d3175 = 5 + c31721 * 1;
        if (d3174 >= 0 && d3174 < 1 && d3175 >= 0 && d3175 < 16005) r121[d3174 * 16005 + d3175 * 1] = r117[i3171];
    }
    /* convert [convert_element_type] -> r122 */
    for (long i3176 = 0; i3176 < 1; ++i3176) {
        r122[i3176] = (int32_t)r13[0];
    }
    /* pad [pad] -> r123 */
    for (long i3177 = 0; i3177 < 16389; ++i3177) {
        r123[i3177] = r122[0];
    }
    for (long i3178 = 0; i3178 < 16005; ++i3178) {
        long t3180 = i3178;
        long c31790 = t3180 / 16005; t3180 %= 16005;
        long c31791 = t3180;
        long d3181 = 0 + c31790 * 1;
        long d3182 = 0 + c31791 * 1;
        if (d3181 >= 0 && d3181 < 1 && d3182 >= 0 && d3182 < 16389) r123[d3181 * 16389 + d3182 * 1] = r121[i3178];
    }
    /* iota [iota] -> r124 */
    for (long i3183 = 0; i3183 < 1024; ++i3183) {
        long t3185 = i3183;
        long c31840 = t3185;
        r124[i3183] = (int32_t)c31840;
    }
    /* broadcast [broadcast_in_dim] -> r125 */
    for (long i3186 = 0; i3186 < 1024; ++i3186) {
        long t3188 = i3186;
        long c31870 = t3188 / 1; t3188 %= 1;
        long c31871 = t3188;
        r125[i3186] = r124[c31870 * 1];
    }
    /* iota [iota] -> r126 */
    for (long i3189 = 0; i3189 < 6; ++i3189) {
        long t3191 = i3189;
        long c31900 = t3191;
        r126[i3189] = (int32_t)c31900;
    }
    /* broadcast [broadcast_in_dim] -> r127 */
    for (long i3192 = 0; i3192 < 6; ++i3192) {
        long t3194 = i3192;
        long c31930 = t3194 / 6; t3194 %= 6;
        long c31931 = t3194;
        r127[i3192] = r126[c31931 * 1];
    }
    /* add [add] -> r128 */
    for (long i3195 = 0; i3195 < 6144; ++i3195) {
        long t3197 = i3195;
        long c31960 = t3197 / 6; t3197 %= 6;
        long c31961 = t3197;
        r128[i3195] = add32(r125[c31960 * 1], r127[c31961 * 1]);
    }
    /* iota [iota] -> r129 */
    for (long i3198 = 0; i3198 < 16; ++i3198) {
        long t3200 = i3198;
        long c31990 = t3200;
        r129[i3198] = (int32_t)c31990;
    }
    /* shl [mul] -> r130 */
    for (long i3201 = 0; i3201 < 16; ++i3201) {
        r130[i3201] = shl32(r129[i3201], 10);
    }
    /* loop [scan] -> r212 */
    memcpy(r131, r123, sizeof(int32_t) * 16389);
    memcpy(r132, r128, sizeof(int32_t) * 6144);
    memcpy(r133, r119, sizeof(int32_t) * 6);
    for (long t3202 = 0; t3202 < 16; ++t3202) {
        memcpy(r134, r130 + t3202 * 1, sizeof(int32_t) * 1);
        /* lt [lt] -> r135 */
        for (long i4203 = 0; i4203 < 1; ++i4203) {
            r135[i4203] = r134[0] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r137 */
        for (long i4204 = 0; i4204 < 1; ++i4204) {
            r137[i4204] = add32(r134[0], r136[0]);
        }
        /* select_n [select_n] -> r138 */
        for (long i4205 = 0; i4205 < 1; ++i4205) {
            r138[i4205] = r135[0] == 0 ? r134[0] : (r137[0]);
        }
        /* dynamic_slice [dynamic_slice] -> r139 */
        long s4206 = clamp_start((long)r13[0], 1, 1);
        long s4207 = clamp_start((long)r138[0], 16389, 1029);
        {
        for (long i4208 = 0; i4208 < 1029; ++i4208) {
            long t4210 = i4208;
            long c42090 = t4210 / 1029; t4210 %= 1029;
            long c42091 = t4210;
            r139[i4208] = r131[(s4206 + c42090) * 16389 + (s4207 + c42091) * 1];
        }
        }
        /* lt [lt] -> r140 */
        for (long i4211 = 0; i4211 < 6144; ++i4211) {
            r140[i4211] = r132[i4211] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r142 */
        for (long i4212 = 0; i4212 < 6144; ++i4212) {
            r142[i4212] = add32(r132[i4212], r141[0]);
        }
        /* select_n [select_n] -> r143 */
        for (long i4213 = 0; i4213 < 6144; ++i4213) {
            r143[i4213] = r140[i4213] == 0 ? r132[i4213] : (r142[i4213]);
        }
        /* broadcast [broadcast_in_dim] -> r144 */
        for (long i4214 = 0; i4214 < 6144; ++i4214) {
            long t4216 = i4214;
            long c42150 = t4216 / 6; t4216 %= 6;
            long c42151 = t4216 / 1; t4216 %= 1;
            long c42152 = t4216;
            r144[i4214] = r143[c42150 * 6 + c42151 * 1];
        }
        /* gather [gather] -> r145 */
        for (long i4217 = 0; i4217 < 6144; ++i4217) {
            long t4219 = i4217;
            long c42180 = t4219 / 6144; t4219 %= 6144;
            long c42181 = t4219 / 6; t4219 %= 6;
            long c42182 = t4219;
            long row4220 = c42181 * 6 + c42182 * 1;
            long s4221 = clamp_start((long)r144[row4220 + 0], 1029, 1);
            r145[i4217] = r139[c42180 * 1029 + s4221 * 1];
        }
        /* broadcast [broadcast_in_dim] -> r146 */
        for (long i4222 = 0; i4222 < 6144; ++i4222) {
            long t4224 = i4222;
            long c42230 = t4224 / 6144; t4224 %= 6144;
            long c42231 = t4224 / 6144; t4224 %= 6144;
            long c42232 = t4224 / 6; t4224 %= 6;
            long c42233 = t4224;
            r146[i4222] = r145[c42232 * 6 + c42233 * 1];
        }
        /* add [add] -> r147 */
        for (long i4225 = 0; i4225 < 6144; ++i4225) {
            long t4227 = i4225;
            long c42260 = t4227 / 6144; t4227 %= 6144;
            long c42261 = t4227 / 6144; t4227 %= 6144;
            long c42262 = t4227 / 6; t4227 %= 6;
            long c42263 = t4227;
            r147[i4225] = add32(r133[c42263 * 1], r146[c42262 * 6 + c42263 * 1]);
        }
        /* convert [convert_element_type] -> r148 */
        for (long i4228 = 0; i4228 < 1; ++i4228) {
            r148[i4228] = (int32_t)r42[0];
        }
        /* max [max] -> r149 */
        for (long i4229 = 0; i4229 < 6144; ++i4229) {
            r149[i4229] = max32(r148[0], r147[i4229]);
        }
        /* convert [convert_element_type] -> r150 */
        for (long i4230 = 0; i4230 < 1; ++i4230) {
            r150[i4230] = (int32_t)r43[0];
        }
        /* min [min] -> r151 */
        for (long i4231 = 0; i4231 < 6144; ++i4231) {
            r151[i4231] = min32(r150[0], r149[i4231]);
        }
        /* sub [sub] -> r152 */
        for (long i4232 = 0; i4232 < 6144; ++i4232) {
            long t4234 = i4232;
            long c42330 = t4234 / 6144; t4234 %= 6144;
            long c42331 = t4234 / 6144; t4234 %= 6144;
            long c42332 = t4234 / 6; t4234 %= 6;
            long c42333 = t4234;
            r152[i4232] = sub32(r133[c42333 * 1], r146[c42332 * 6 + c42333 * 1]);
        }
        /* convert [convert_element_type] -> r153 */
        for (long i4235 = 0; i4235 < 1; ++i4235) {
            r153[i4235] = (int32_t)r42[0];
        }
        /* max [max] -> r154 */
        for (long i4236 = 0; i4236 < 6144; ++i4236) {
            r154[i4236] = max32(r153[0], r152[i4236]);
        }
        /* convert [convert_element_type] -> r155 */
        for (long i4237 = 0; i4237 < 1; ++i4237) {
            r155[i4237] = (int32_t)r43[0];
        }
        /* min [min] -> r156 */
        for (long i4238 = 0; i4238 < 6144; ++i4238) {
            r156[i4238] = min32(r155[0], r154[i4238]);
        }
        /* abs [abs] -> r157 */
        for (long i4239 = 0; i4239 < 6144; ++i4239) {
            r157[i4239] = abs32(r151[i4239]);
        }
        /* reduce_max [reduce_max] -> r158 */
        for (long i4240 = 0; i4240 < 1024; ++i4240) {
            r158[i4240] = (-2147483647 - 1);
        }
        for (long i4241 = 0; i4241 < 6144; ++i4241) {
            long t4243 = i4241;
            long c42420 = t4243 / 6144; t4243 %= 6144;
            long c42421 = t4243 / 6144; t4243 %= 6144;
            long c42422 = t4243 / 6; t4243 %= 6;
            long c42423 = t4243;
            r158[c42420 * 1024 + c42421 * 1024 + c42422 * 1] = max32(r158[c42420 * 1024 + c42421 * 1024 + c42422 * 1], r157[i4241]);
        }
        /* sub [sub] -> r159 */
        for (long i4244 = 0; i4244 < 1024; ++i4244) {
            r159[i4244] = sub32(r158[i4244], r55[0]);
        }
        /* loop [scan] -> r181 */
        memcpy(r160, r151, sizeof(int32_t) * 6144);
        memcpy(r161, r55, sizeof(int32_t) * 1);
        memcpy(r162, r13, sizeof(int32_t) * 1);
        memcpy(r163, r159, sizeof(int32_t) * 1024);
        memcpy(r164, r158, sizeof(int32_t) * 1024);
        for (long t4245 = 0; t4245 < 12; ++t4245) {
            /* add [add] -> r165 */
            for (long i5246 = 0; i5246 < 1; ++i5246) {
                r165[i5246] = add32(r162[0], r9[0]);
            }
            /* add [add] -> r166 */
            for (long i5247 = 0; i5247 < 1024; ++i5247) {
                r166[i5247] = add32(r163[i5247], r164[i5247]);
            }
            /* shra [shift_right_arithmetic] -> r167 */
            for (long i5248 = 0; i5248 < 1024; ++i5248) {
                r167[i5248] = asr32(r166[i5248], 1);
            }
            /* broadcast [broadcast_in_dim] -> r168 */
            for (long i5249 = 0; i5249 < 1024; ++i5249) {
                long t5251 = i5249;
                long c52500 = t5251 / 1024; t5251 %= 1024;
                long c52501 = t5251 / 1024; t5251 %= 1024;
                long c52502 = t5251 / 1; t5251 %= 1;
                long c52503 = t5251;
                r168[i5249] = r167[c52502 * 1];
            }
            /* sub [sub] -> r169 */
            for (long i5252 = 0; i5252 < 6144; ++i5252) {
                long t5254 = i5252;
                long c52530 = t5254 / 6144; t5254 %= 6144;
                long c52531 = t5254 / 6144; t5254 %= 6144;
                long c52532 = t5254 / 6; t5254 %= 6;
                long c52533 = t5254;
                r169[i5252] = sub32(r160[c52532 * 6 + c52533 * 1], r168[c52532 * 1]);
            }
            /* max [max] -> r170 */
            for (long i5255 = 0; i5255 < 6144; ++i5255) {
                r170[i5255] = max32(r169[i5255], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r171 */
            for (long i5256 = 0; i5256 < 1024; ++i5256) {
                r171[i5256] = 0;
            }
            for (long i5257 = 0; i5257 < 6144; ++i5257) {
                long t5259 = i5257;
                long c52580 = t5259 / 6144; t5259 %= 6144;
                long c52581 = t5259 / 6144; t5259 %= 6144;
                long c52582 = t5259 / 6; t5259 %= 6;
                long c52583 = t5259;
                r171[c52580 * 1024 + c52581 * 1024 + c52582 * 1] = add32(r171[c52580 * 1024 + c52581 * 1024 + c52582 * 1], r170[i5257]);
            }
            /* neg [neg] -> r172 */
            for (long i5260 = 0; i5260 < 6144; ++i5260) {
                r172[i5260] = neg32(r160[i5260]);
            }
            /* broadcast [broadcast_in_dim] -> r173 */
            for (long i5261 = 0; i5261 < 1024; ++i5261) {
                long t5263 = i5261;
                long c52620 = t5263 / 1024; t5263 %= 1024;
                long c52621 = t5263 / 1024; t5263 %= 1024;
                long c52622 = t5263 / 1; t5263 %= 1;
                long c52623 = t5263;
                r173[i5261] = r167[c52622 * 1];
            }
            /* sub [sub] -> r174 */
            for (long i5264 = 0; i5264 < 6144; ++i5264) {
                long t5266 = i5264;
                long c52650 = t5266 / 6144; t5266 %= 6144;
                long c52651 = t5266 / 6144; t5266 %= 6144;
                long c52652 = t5266 / 6; t5266 %= 6;
                long c52653 = t5266;
                r174[i5264] = sub32(r172[c52652 * 6 + c52653 * 1], r173[c52652 * 1]);
            }
            /* max [max] -> r175 */
            for (long i5267 = 0; i5267 < 6144; ++i5267) {
                r175[i5267] = max32(r174[i5267], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r176 */
            for (long i5268 = 0; i5268 < 1024; ++i5268) {
                r176[i5268] = 0;
            }
            for (long i5269 = 0; i5269 < 6144; ++i5269) {
                long t5271 = i5269;
                long c52700 = t5271 / 6144; t5271 %= 6144;
                long c52701 = t5271 / 6144; t5271 %= 6144;
                long c52702 = t5271 / 6; t5271 %= 6;
                long c52703 = t5271;
                r176[c52700 * 1024 + c52701 * 1024 + c52702 * 1] = add32(r176[c52700 * 1024 + c52701 * 1024 + c52702 * 1], r175[i5269]);
            }
            /* add [add] -> r177 */
            for (long i5272 = 0; i5272 < 1024; ++i5272) {
                r177[i5272] = add32(r171[i5272], r176[i5272]);
            }
            /* gt [gt] -> r178 */
            for (long i5273 = 0; i5273 < 1024; ++i5273) {
                r178[i5273] = r177[i5273] > r161[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r179 */
            for (long i5274 = 0; i5274 < 1024; ++i5274) {
                r179[i5274] = r178[i5274] == 0 ? r163[i5274] : (r167[i5274]);
            }
            /* select_n [select_n] -> r180 */
            for (long i5275 = 0; i5275 < 1024; ++i5275) {
                r180[i5275] = r178[i5275] == 0 ? r167[i5275] : (r164[i5275]);
            }
            memcpy(r162, r165, sizeof(int32_t) * 1);
            memcpy(r163, r179, sizeof(int32_t) * 1024);
            memcpy(r164, r180, sizeof(int32_t) * 1024);
        }
        memcpy(r181, r162, sizeof(int32_t) * 1);
        memcpy(r182, r163, sizeof(int32_t) * 1024);
        memcpy(r183, r164, sizeof(int32_t) * 1024);
        /* abs [abs] -> r184 */
        for (long i5276 = 0; i5276 < 6144; ++i5276) {
            r184[i5276] = abs32(r156[i5276]);
        }
        /* reduce_max [reduce_max] -> r185 */
        for (long i5277 = 0; i5277 < 1024; ++i5277) {
            r185[i5277] = (-2147483647 - 1);
        }
        for (long i5278 = 0; i5278 < 6144; ++i5278) {
            long t5280 = i5278;
            long c52790 = t5280 / 6144; t5280 %= 6144;
            long c52791 = t5280 / 6144; t5280 %= 6144;
            long c52792 = t5280 / 6; t5280 %= 6;
            long c52793 = t5280;
            r185[c52790 * 1024 + c52791 * 1024 + c52792 * 1] = max32(r185[c52790 * 1024 + c52791 * 1024 + c52792 * 1], r184[i5278]);
        }
        /* sub [sub] -> r186 */
        for (long i5281 = 0; i5281 < 1024; ++i5281) {
            r186[i5281] = sub32(r185[i5281], r55[0]);
        }
        /* loop [scan] -> r208 */
        memcpy(r187, r156, sizeof(int32_t) * 6144);
        memcpy(r188, r55, sizeof(int32_t) * 1);
        memcpy(r189, r13, sizeof(int32_t) * 1);
        memcpy(r190, r186, sizeof(int32_t) * 1024);
        memcpy(r191, r185, sizeof(int32_t) * 1024);
        for (long t5282 = 0; t5282 < 12; ++t5282) {
            /* add [add] -> r192 */
            for (long i6283 = 0; i6283 < 1; ++i6283) {
                r192[i6283] = add32(r189[0], r9[0]);
            }
            /* add [add] -> r193 */
            for (long i6284 = 0; i6284 < 1024; ++i6284) {
                r193[i6284] = add32(r190[i6284], r191[i6284]);
            }
            /* shra [shift_right_arithmetic] -> r194 */
            for (long i6285 = 0; i6285 < 1024; ++i6285) {
                r194[i6285] = asr32(r193[i6285], 1);
            }
            /* broadcast [broadcast_in_dim] -> r195 */
            for (long i6286 = 0; i6286 < 1024; ++i6286) {
                long t6288 = i6286;
                long c62870 = t6288 / 1024; t6288 %= 1024;
                long c62871 = t6288 / 1024; t6288 %= 1024;
                long c62872 = t6288 / 1; t6288 %= 1;
                long c62873 = t6288;
                r195[i6286] = r194[c62872 * 1];
            }
            /* sub [sub] -> r196 */
            for (long i6289 = 0; i6289 < 6144; ++i6289) {
                long t6291 = i6289;
                long c62900 = t6291 / 6144; t6291 %= 6144;
                long c62901 = t6291 / 6144; t6291 %= 6144;
                long c62902 = t6291 / 6; t6291 %= 6;
                long c62903 = t6291;
                r196[i6289] = sub32(r187[c62902 * 6 + c62903 * 1], r195[c62902 * 1]);
            }
            /* max [max] -> r197 */
            for (long i6292 = 0; i6292 < 6144; ++i6292) {
                r197[i6292] = max32(r196[i6292], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r198 */
            for (long i6293 = 0; i6293 < 1024; ++i6293) {
                r198[i6293] = 0;
            }
            for (long i6294 = 0; i6294 < 6144; ++i6294) {
                long t6296 = i6294;
                long c62950 = t6296 / 6144; t6296 %= 6144;
                long c62951 = t6296 / 6144; t6296 %= 6144;
                long c62952 = t6296 / 6; t6296 %= 6;
                long c62953 = t6296;
                r198[c62950 * 1024 + c62951 * 1024 + c62952 * 1] = add32(r198[c62950 * 1024 + c62951 * 1024 + c62952 * 1], r197[i6294]);
            }
            /* neg [neg] -> r199 */
            for (long i6297 = 0; i6297 < 6144; ++i6297) {
                r199[i6297] = neg32(r187[i6297]);
            }
            /* broadcast [broadcast_in_dim] -> r200 */
            for (long i6298 = 0; i6298 < 1024; ++i6298) {
                long t6300 = i6298;
                long c62990 = t6300 / 1024; t6300 %= 1024;
                long c62991 = t6300 / 1024; t6300 %= 1024;
                long c62992 = t6300 / 1; t6300 %= 1;
                long c62993 = t6300;
                r200[i6298] = r194[c62992 * 1];
            }
            /* sub [sub] -> r201 */
            for (long i6301 = 0; i6301 < 6144; ++i6301) {
                long t6303 = i6301;
                long c63020 = t6303 / 6144; t6303 %= 6144;
                long c63021 = t6303 / 6144; t6303 %= 6144;
                long c63022 = t6303 / 6; t6303 %= 6;
                long c63023 = t6303;
                r201[i6301] = sub32(r199[c63022 * 6 + c63023 * 1], r200[c63022 * 1]);
            }
            /* max [max] -> r202 */
            for (long i6304 = 0; i6304 < 6144; ++i6304) {
                r202[i6304] = max32(r201[i6304], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r203 */
            for (long i6305 = 0; i6305 < 1024; ++i6305) {
                r203[i6305] = 0;
            }
            for (long i6306 = 0; i6306 < 6144; ++i6306) {
                long t6308 = i6306;
                long c63070 = t6308 / 6144; t6308 %= 6144;
                long c63071 = t6308 / 6144; t6308 %= 6144;
                long c63072 = t6308 / 6; t6308 %= 6;
                long c63073 = t6308;
                r203[c63070 * 1024 + c63071 * 1024 + c63072 * 1] = add32(r203[c63070 * 1024 + c63071 * 1024 + c63072 * 1], r202[i6306]);
            }
            /* add [add] -> r204 */
            for (long i6309 = 0; i6309 < 1024; ++i6309) {
                r204[i6309] = add32(r198[i6309], r203[i6309]);
            }
            /* gt [gt] -> r205 */
            for (long i6310 = 0; i6310 < 1024; ++i6310) {
                r205[i6310] = r204[i6310] > r188[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r206 */
            for (long i6311 = 0; i6311 < 1024; ++i6311) {
                r206[i6311] = r205[i6311] == 0 ? r190[i6311] : (r194[i6311]);
            }
            /* select_n [select_n] -> r207 */
            for (long i6312 = 0; i6312 < 1024; ++i6312) {
                r207[i6312] = r205[i6312] == 0 ? r194[i6312] : (r191[i6312]);
            }
            memcpy(r189, r192, sizeof(int32_t) * 1);
            memcpy(r190, r206, sizeof(int32_t) * 1024);
            memcpy(r191, r207, sizeof(int32_t) * 1024);
        }
        memcpy(r208, r189, sizeof(int32_t) * 1);
        memcpy(r209, r190, sizeof(int32_t) * 1024);
        memcpy(r210, r191, sizeof(int32_t) * 1024);
        /* sub [sub] -> r211 */
        for (long i6313 = 0; i6313 < 1024; ++i6313) {
            r211[i6313] = sub32(r183[i6313], r210[i6313]);
        }
        memcpy(r212 + t3202 * 1024, r211, sizeof(int32_t) * 1024);
    }
    /* transpose [transpose] -> r213 */
    for (long i6314 = 0; i6314 < 16384; ++i6314) {
        long t6316 = i6314;
        long c63150 = t6316 / 16384; t6316 %= 16384;
        long c63151 = t6316 / 16384; t6316 %= 16384;
        long c63152 = t6316 / 1024; t6316 %= 1024;
        long c63153 = t6316;
        r213[i6314] = r212[c63150 * 1024 + c63151 * 1024 + c63152 * 1024 + c63153 * 1];
    }
    /* reshape [reshape] -> r214 */
    memcpy(r214, r213, sizeof(int32_t) * 16384);
    /* slice [slice] -> r215 */
    for (long i6317 = 0; i6317 < 16000; ++i6317) {
        long t6319 = i6317;
        long c63180 = t6319 / 16000; t6319 %= 16000;
        long c63181 = t6319 / 16000; t6319 %= 16000;
        long c63182 = t6319;
        r215[i6317] = r214[(0 + c63180 * 1) * 16384 + (0 + c63181 * 1) * 16384 + (0 + c63182 * 1) * 1];
    }
    /* transpose [transpose] -> r216 */
    for (long i6320 = 0; i6320 < 16000; ++i6320) {
        long t6322 = i6320;
        long c63210 = t6322 / 16000; t6322 %= 16000;
        long c63211 = t6322 / 16000; t6322 %= 16000;
        long c63212 = t6322;
        r216[i6320] = r215[c63210 * 16000 + c63211 * 16000 + c63212 * 1];
    }
    /* slice [slice] -> r217 */
    for (long i6323 = 0; i6323 < 16000; ++i6323) {
        long t6325 = i6323;
        long c63240 = t6325 / 16000; t6325 %= 16000;
        long c63241 = t6325 / 16000; t6325 %= 16000;
        long c63242 = t6325;
        r217[i6323] = r216[(0 + c63240 * 1) * 16000 + (0 + c63241 * 1) * 16000 + (0 + c63242 * 1) * 1];
    }
    /* reshape [squeeze] -> r218 */
    memcpy(r218, r217, sizeof(int32_t) * 16000);
    /* shra [shift_right_arithmetic] -> r219 */
    for (long i6326 = 0; i6326 < 16000; ++i6326) {
        r219[i6326] = asr32(r218[i6326], 1);
    }
    /* convert [convert_element_type] -> r222 */
    for (long i6327 = 0; i6327 < 1; ++i6327) {
        r222[i6327] = (int32_t)r220[0];
    }
    /* max [max] -> r223 */
    for (long i6328 = 0; i6328 < 16000; ++i6328) {
        r223[i6328] = max32(r222[0], r219[i6328]);
    }
    /* convert [convert_element_type] -> r224 */
    for (long i6329 = 0; i6329 < 1; ++i6329) {
        r224[i6329] = (int32_t)r221[0];
    }
    /* min [min] -> r225 */
    for (long i6330 = 0; i6330 < 16000; ++i6330) {
        r225[i6330] = min32(r224[0], r223[i6330]);
    }
    /* iota [iota] -> r226 */
    for (long i6331 = 0; i6331 < 8000; ++i6331) {
        long t6333 = i6331;
        long c63320 = t6333;
        r226[i6331] = (int32_t)c63320;
    }
    /* shl [mul] -> r227 */
    for (long i6334 = 0; i6334 < 8000; ++i6334) {
        r227[i6334] = shl32(r226[i6334], 1);
    }
    /* add [add] -> r228 */
    for (long i6335 = 0; i6335 < 8000; ++i6335) {
        r228[i6335] = add32(r13[0], r227[i6335]);
    }
    /* broadcast [broadcast_in_dim] -> r229 */
    for (long i6336 = 0; i6336 < 8000; ++i6336) {
        long t6338 = i6336;
        long c63370 = t6338 / 1; t6338 %= 1;
        long c63371 = t6338;
        r229[i6336] = r228[c63370 * 1];
    }
    /* gather [gather] -> r230 */
    for (long i6339 = 0; i6339 < 8000; ++i6339) {
        long t6341 = i6339;
        long c63400 = t6341 / 8000; t6341 %= 8000;
        long c63401 = t6341;
        long row6342 = c63401 * 1;
        long s6343 = clamp_start((long)r229[row6342 + 0], 16000, 1);
        r230[i6339] = r225[c63400 * 16000 + s6343 * 1];
    }
    /* shl [shift_left] -> r231 */
    for (long i6344 = 0; i6344 < 8000; ++i6344) {
        r231[i6344] = shl32(r230[i6344], 1);
    }
    /* rev [rev] -> r232 */
    for (long i6345 = 0; i6345 < 80; ++i6345) {
        long t6347 = i6345;
        long c63460 = t6347 / 16; t6347 %= 16;
        long c63461 = t6347;
        r232[i6345] = r1[c63460 * 16 + (16 - 1 - c63461) * 1];
    }
    /* reshape [reshape] -> r233 */
    memcpy(r233, r232, sizeof(int32_t) * 80);
    /* convert [convert_element_type] -> r234 */
    for (long i6348 = 0; i6348 < 1; ++i6348) {
        r234[i6348] = (int32_t)r13[0];
    }
    /* pad [pad] -> r235 */
    for (long i6349 = 0; i6349 < 8015; ++i6349) {
        r235[i6349] = r234[0];
    }
    for (long i6350 = 0; i6350 < 8000; ++i6350) {
        long t6352 = i6350;
        long c63510 = t6352 / 8000; t6352 %= 8000;
        long c63511 = t6352;
        long d6353 = 0 + c63510 * 1;
        long d6354 = 15 + c63511 * 1;
        if (d6353 >= 0 && d6353 < 1 && d6354 >= 0 && d6354 < 8015) r235[d6353 * 8015 + d6354 * 1] = r231[i6350];
    }
    /* convert [convert_element_type] -> r236 */
    for (long i6355 = 0; i6355 < 1; ++i6355) {
        r236[i6355] = (int32_t)r13[0];
    }
    /* pad [pad] -> r237 */
    for (long i6356 = 0; i6356 < 8207; ++i6356) {
        r237[i6356] = r236[0];
    }
    for (long i6357 = 0; i6357 < 8015; ++i6357) {
        long t6359 = i6357;
        long c63580 = t6359 / 8015; t6359 %= 8015;
        long c63581 = t6359;
        long d6360 = 0 + c63580 * 1;
        long d6361 = 0 + c63581 * 1;
        if (d6360 >= 0 && d6360 < 1 && d6361 >= 0 && d6361 < 8207) r237[d6360 * 8207 + d6361 * 1] = r235[i6357];
    }
    /* iota [iota] -> r238 */
    for (long i6362 = 0; i6362 < 1024; ++i6362) {
        long t6364 = i6362;
        long c63630 = t6364;
        r238[i6362] = (int32_t)c63630;
    }
    /* broadcast [broadcast_in_dim] -> r239 */
    for (long i6365 = 0; i6365 < 1024; ++i6365) {
        long t6367 = i6365;
        long c63660 = t6367 / 1; t6367 %= 1;
        long c63661 = t6367;
        r239[i6365] = r238[c63660 * 1];
    }
    /* iota [iota] -> r240 */
    for (long i6368 = 0; i6368 < 16; ++i6368) {
        long t6370 = i6368;
        long c63690 = t6370;
        r240[i6368] = (int32_t)c63690;
    }
    /* broadcast [broadcast_in_dim] -> r241 */
    for (long i6371 = 0; i6371 < 16; ++i6371) {
        long t6373 = i6371;
        long c63720 = t6373 / 16; t6373 %= 16;
        long c63721 = t6373;
        r241[i6371] = r240[c63721 * 1];
    }
    /* add [add] -> r242 */
    for (long i6374 = 0; i6374 < 16384; ++i6374) {
        long t6376 = i6374;
        long c63750 = t6376 / 16; t6376 %= 16;
        long c63751 = t6376;
        r242[i6374] = add32(r239[c63750 * 1], r241[c63751 * 1]);
    }
    /* iota [iota] -> r243 */
    for (long i6377 = 0; i6377 < 8; ++i6377) {
        long t6379 = i6377;
        long c63780 = t6379;
        r243[i6377] = (int32_t)c63780;
    }
    /* shl [mul] -> r244 */
    for (long i6380 = 0; i6380 < 8; ++i6380) {
        r244[i6380] = shl32(r243[i6380], 10);
    }
    /* loop [scan] -> r325 */
    memcpy(r245, r237, sizeof(int32_t) * 8207);
    memcpy(r246, r242, sizeof(int32_t) * 16384);
    memcpy(r247, r233, sizeof(int32_t) * 80);
    for (long t6381 = 0; t6381 < 8; ++t6381) {
        memcpy(r248, r244 + t6381 * 1, sizeof(int32_t) * 1);
        /* lt [lt] -> r249 */
        for (long i7382 = 0; i7382 < 1; ++i7382) {
            r249[i7382] = r248[0] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r251 */
        for (long i7383 = 0; i7383 < 1; ++i7383) {
            r251[i7383] = add32(r248[0], r250[0]);
        }
        /* select_n [select_n] -> r252 */
        for (long i7384 = 0; i7384 < 1; ++i7384) {
            r252[i7384] = r249[0] == 0 ? r248[0] : (r251[0]);
        }
        /* dynamic_slice [dynamic_slice] -> r253 */
        long s7385 = clamp_start((long)r13[0], 1, 1);
        long s7386 = clamp_start((long)r252[0], 8207, 1039);
        {
        for (long i7387 = 0; i7387 < 1039; ++i7387) {
            long t7389 = i7387;
            long c73880 = t7389 / 1039; t7389 %= 1039;
            long c73881 = t7389;
            r253[i7387] = r245[(s7385 + c73880) * 8207 + (s7386 + c73881) * 1];
        }
        }
        /* lt [lt] -> r254 */
        for (long i7390 = 0; i7390 < 16384; ++i7390) {
            r254[i7390] = r246[i7390] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r255 */
        for (long i7391 = 0; i7391 < 16384; ++i7391) {
            r255[i7391] = add32(r246[i7391], r35[0]);
        }
        /* select_n [select_n] -> r256 */
        for (long i7392 = 0; i7392 < 16384; ++i7392) {
            r256[i7392] = r254[i7392] == 0 ? r246[i7392] : (r255[i7392]);
        }
        /* broadcast [broadcast_in_dim] -> r257 */
        for (long i7393 = 0; i7393 < 16384; ++i7393) {
            long t7395 = i7393;
            long c73940 = t7395 / 16; t7395 %= 16;
            long c73941 = t7395 / 1; t7395 %= 1;
            long c73942 = t7395;
            r257[i7393] = r256[c73940 * 16 + c73941 * 1];
        }
        /* gather [gather] -> r258 */
        for (long i7396 = 0; i7396 < 16384; ++i7396) {
            long t7398 = i7396;
            long c73970 = t7398 / 16384; t7398 %= 16384;
            long c73971 = t7398 / 16; t7398 %= 16;
            long c73972 = t7398;
            long row7399 = c73971 * 16 + c73972 * 1;
            long s7400 = clamp_start((long)r257[row7399 + 0], 1039, 1);
            r258[i7396] = r253[c73970 * 1039 + s7400 * 1];
        }
        /* broadcast [broadcast_in_dim] -> r259 */
        for (long i7401 = 0; i7401 < 16384; ++i7401) {
            long t7403 = i7401;
            long c74020 = t7403 / 16384; t7403 %= 16384;
            long c74021 = t7403 / 16384; t7403 %= 16384;
            long c74022 = t7403 / 16; t7403 %= 16;
            long c74023 = t7403;
            r259[i7401] = r258[c74022 * 16 + c74023 * 1];
        }
        /* add [add] -> r260 */
        for (long i7404 = 0; i7404 < 81920; ++i7404) {
            long t7406 = i7404;
            long c74050 = t7406 / 16384; t7406 %= 16384;
            long c74051 = t7406 / 16384; t7406 %= 16384;
            long c74052 = t7406 / 16; t7406 %= 16;
            long c74053 = t7406;
            r260[i7404] = add32(r247[c74050 * 16 + c74053 * 1], r259[c74052 * 16 + c74053 * 1]);
        }
        /* convert [convert_element_type] -> r261 */
        for (long i7407 = 0; i7407 < 1; ++i7407) {
            r261[i7407] = (int32_t)r42[0];
        }
        /* max [max] -> r262 */
        for (long i7408 = 0; i7408 < 81920; ++i7408) {
            r262[i7408] = max32(r261[0], r260[i7408]);
        }
        /* convert [convert_element_type] -> r263 */
        for (long i7409 = 0; i7409 < 1; ++i7409) {
            r263[i7409] = (int32_t)r43[0];
        }
        /* min [min] -> r264 */
        for (long i7410 = 0; i7410 < 81920; ++i7410) {
            r264[i7410] = min32(r263[0], r262[i7410]);
        }
        /* sub [sub] -> r265 */
        for (long i7411 = 0; i7411 < 81920; ++i7411) {
            long t7413 = i7411;
            long c74120 = t7413 / 16384; t7413 %= 16384;
            long c74121 = t7413 / 16384; t7413 %= 16384;
            long c74122 = t7413 / 16; t7413 %= 16;
            long c74123 = t7413;
            r265[i7411] = sub32(r247[c74120 * 16 + c74123 * 1], r259[c74122 * 16 + c74123 * 1]);
        }
        /* convert [convert_element_type] -> r266 */
        for (long i7414 = 0; i7414 < 1; ++i7414) {
            r266[i7414] = (int32_t)r42[0];
        }
        /* max [max] -> r267 */
        for (long i7415 = 0; i7415 < 81920; ++i7415) {
            r267[i7415] = max32(r266[0], r265[i7415]);
        }
        /* convert [convert_element_type] -> r268 */
        for (long i7416 = 0; i7416 < 1; ++i7416) {
            r268[i7416] = (int32_t)r43[0];
        }
        /* min [min] -> r269 */
        for (long i7417 = 0; i7417 < 81920; ++i7417) {
            r269[i7417] = min32(r268[0], r267[i7417]);
        }
        /* abs [abs] -> r270 */
        for (long i7418 = 0; i7418 < 81920; ++i7418) {
            r270[i7418] = abs32(r264[i7418]);
        }
        /* reduce_max [reduce_max] -> r271 */
        for (long i7419 = 0; i7419 < 5120; ++i7419) {
            r271[i7419] = (-2147483647 - 1);
        }
        for (long i7420 = 0; i7420 < 81920; ++i7420) {
            long t7422 = i7420;
            long c74210 = t7422 / 16384; t7422 %= 16384;
            long c74211 = t7422 / 16384; t7422 %= 16384;
            long c74212 = t7422 / 16; t7422 %= 16;
            long c74213 = t7422;
            r271[c74210 * 1024 + c74211 * 1024 + c74212 * 1] = max32(r271[c74210 * 1024 + c74211 * 1024 + c74212 * 1], r270[i7420]);
        }
        /* sub [sub] -> r272 */
        for (long i7423 = 0; i7423 < 5120; ++i7423) {
            r272[i7423] = sub32(r271[i7423], r55[0]);
        }
        /* loop [scan] -> r294 */
        memcpy(r273, r264, sizeof(int32_t) * 81920);
        memcpy(r274, r55, sizeof(int32_t) * 1);
        memcpy(r275, r13, sizeof(int32_t) * 1);
        memcpy(r276, r272, sizeof(int32_t) * 5120);
        memcpy(r277, r271, sizeof(int32_t) * 5120);
        for (long t7424 = 0; t7424 < 12; ++t7424) {
            /* add [add] -> r278 */
            for (long i8425 = 0; i8425 < 1; ++i8425) {
                r278[i8425] = add32(r275[0], r9[0]);
            }
            /* add [add] -> r279 */
            for (long i8426 = 0; i8426 < 5120; ++i8426) {
                r279[i8426] = add32(r276[i8426], r277[i8426]);
            }
            /* shra [shift_right_arithmetic] -> r280 */
            for (long i8427 = 0; i8427 < 5120; ++i8427) {
                r280[i8427] = asr32(r279[i8427], 1);
            }
            /* broadcast [broadcast_in_dim] -> r281 */
            for (long i8428 = 0; i8428 < 5120; ++i8428) {
                long t8430 = i8428;
                long c84290 = t8430 / 1024; t8430 %= 1024;
                long c84291 = t8430 / 1024; t8430 %= 1024;
                long c84292 = t8430 / 1; t8430 %= 1;
                long c84293 = t8430;
                r281[i8428] = r280[c84290 * 1024 + c84292 * 1];
            }
            /* sub [sub] -> r282 */
            for (long i8431 = 0; i8431 < 81920; ++i8431) {
                long t8433 = i8431;
                long c84320 = t8433 / 16384; t8433 %= 16384;
                long c84321 = t8433 / 16384; t8433 %= 16384;
                long c84322 = t8433 / 16; t8433 %= 16;
                long c84323 = t8433;
                r282[i8431] = sub32(r273[c84320 * 16384 + c84322 * 16 + c84323 * 1], r281[c84320 * 1024 + c84322 * 1]);
            }
            /* max [max] -> r283 */
            for (long i8434 = 0; i8434 < 81920; ++i8434) {
                r283[i8434] = max32(r282[i8434], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r284 */
            for (long i8435 = 0; i8435 < 5120; ++i8435) {
                r284[i8435] = 0;
            }
            for (long i8436 = 0; i8436 < 81920; ++i8436) {
                long t8438 = i8436;
                long c84370 = t8438 / 16384; t8438 %= 16384;
                long c84371 = t8438 / 16384; t8438 %= 16384;
                long c84372 = t8438 / 16; t8438 %= 16;
                long c84373 = t8438;
                r284[c84370 * 1024 + c84371 * 1024 + c84372 * 1] = add32(r284[c84370 * 1024 + c84371 * 1024 + c84372 * 1], r283[i8436]);
            }
            /* neg [neg] -> r285 */
            for (long i8439 = 0; i8439 < 81920; ++i8439) {
                r285[i8439] = neg32(r273[i8439]);
            }
            /* broadcast [broadcast_in_dim] -> r286 */
            for (long i8440 = 0; i8440 < 5120; ++i8440) {
                long t8442 = i8440;
                long c84410 = t8442 / 1024; t8442 %= 1024;
                long c84411 = t8442 / 1024; t8442 %= 1024;
                long c84412 = t8442 / 1; t8442 %= 1;
                long c84413 = t8442;
                r286[i8440] = r280[c84410 * 1024 + c84412 * 1];
            }
            /* sub [sub] -> r287 */
            for (long i8443 = 0; i8443 < 81920; ++i8443) {
                long t8445 = i8443;
                long c84440 = t8445 / 16384; t8445 %= 16384;
                long c84441 = t8445 / 16384; t8445 %= 16384;
                long c84442 = t8445 / 16; t8445 %= 16;
                long c84443 = t8445;
                r287[i8443] = sub32(r285[c84440 * 16384 + c84442 * 16 + c84443 * 1], r286[c84440 * 1024 + c84442 * 1]);
            }
            /* max [max] -> r288 */
            for (long i8446 = 0; i8446 < 81920; ++i8446) {
                r288[i8446] = max32(r287[i8446], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r289 */
            for (long i8447 = 0; i8447 < 5120; ++i8447) {
                r289[i8447] = 0;
            }
            for (long i8448 = 0; i8448 < 81920; ++i8448) {
                long t8450 = i8448;
                long c84490 = t8450 / 16384; t8450 %= 16384;
                long c84491 = t8450 / 16384; t8450 %= 16384;
                long c84492 = t8450 / 16; t8450 %= 16;
                long c84493 = t8450;
                r289[c84490 * 1024 + c84491 * 1024 + c84492 * 1] = add32(r289[c84490 * 1024 + c84491 * 1024 + c84492 * 1], r288[i8448]);
            }
            /* add [add] -> r290 */
            for (long i8451 = 0; i8451 < 5120; ++i8451) {
                r290[i8451] = add32(r284[i8451], r289[i8451]);
            }
            /* gt [gt] -> r291 */
            for (long i8452 = 0; i8452 < 5120; ++i8452) {
                r291[i8452] = r290[i8452] > r274[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r292 */
            for (long i8453 = 0; i8453 < 5120; ++i8453) {
                r292[i8453] = r291[i8453] == 0 ? r276[i8453] : (r280[i8453]);
            }
            /* select_n [select_n] -> r293 */
            for (long i8454 = 0; i8454 < 5120; ++i8454) {
                r293[i8454] = r291[i8454] == 0 ? r280[i8454] : (r277[i8454]);
            }
            memcpy(r275, r278, sizeof(int32_t) * 1);
            memcpy(r276, r292, sizeof(int32_t) * 5120);
            memcpy(r277, r293, sizeof(int32_t) * 5120);
        }
        memcpy(r294, r275, sizeof(int32_t) * 1);
        memcpy(r295, r276, sizeof(int32_t) * 5120);
        memcpy(r296, r277, sizeof(int32_t) * 5120);
        /* abs [abs] -> r297 */
        for (long i8455 = 0; i8455 < 81920; ++i8455) {
            r297[i8455] = abs32(r269[i8455]);
        }
        /* reduce_max [reduce_max] -> r298 */
        for (long i8456 = 0; i8456 < 5120; ++i8456) {
            r298[i8456] = (-2147483647 - 1);
        }
        for (long i8457 = 0; i8457 < 81920; ++i8457) {
            long t8459 = i8457;
            long c84580 = t8459 / 16384; t8459 %= 16384;
            long c84581 = t8459 / 16384; t8459 %= 16384;
            long c84582 = t8459 / 16; t8459 %= 16;
            long c84583 = t8459;
            r298[c84580 * 1024 + c84581 * 1024 + c84582 * 1] = max32(r298[c84580 * 1024 + c84581 * 1024 + c84582 * 1], r297[i8457]);
        }
        /* sub [sub] -> r299 */
        for (long i8460 = 0; i8460 < 5120; ++i8460) {
            r299[i8460] = sub32(r298[i8460], r55[0]);
        }
        /* loop [scan] -> r321 */
        memcpy(r300, r269, sizeof(int32_t) * 81920);
        memcpy(r301, r55, sizeof(int32_t) * 1);
        memcpy(r302, r13, sizeof(int32_t) * 1);
        memcpy(r303, r299, sizeof(int32_t) * 5120);
        memcpy(r304, r298, sizeof(int32_t) * 5120);
        for (long t8461 = 0; t8461 < 12; ++t8461) {
            /* add [add] -> r305 */
            for (long i9462 = 0; i9462 < 1; ++i9462) {
                r305[i9462] = add32(r302[0], r9[0]);
            }
            /* add [add] -> r306 */
            for (long i9463 = 0; i9463 < 5120; ++i9463) {
                r306[i9463] = add32(r303[i9463], r304[i9463]);
            }
            /* shra [shift_right_arithmetic] -> r307 */
            for (long i9464 = 0; i9464 < 5120; ++i9464) {
                r307[i9464] = asr32(r306[i9464], 1);
            }
            /* broadcast [broadcast_in_dim] -> r308 */
            for (long i9465 = 0; i9465 < 5120; ++i9465) {
                long t9467 = i9465;
                long c94660 = t9467 / 1024; t9467 %= 1024;
                long c94661 = t9467 / 1024; t9467 %= 1024;
                long c94662 = t9467 / 1; t9467 %= 1;
                long c94663 = t9467;
                r308[i9465] = r307[c94660 * 1024 + c94662 * 1];
            }
            /* sub [sub] -> r309 */
            for (long i9468 = 0; i9468 < 81920; ++i9468) {
                long t9470 = i9468;
                long c94690 = t9470 / 16384; t9470 %= 16384;
                long c94691 = t9470 / 16384; t9470 %= 16384;
                long c94692 = t9470 / 16; t9470 %= 16;
                long c94693 = t9470;
                r309[i9468] = sub32(r300[c94690 * 16384 + c94692 * 16 + c94693 * 1], r308[c94690 * 1024 + c94692 * 1]);
            }
            /* max [max] -> r310 */
            for (long i9471 = 0; i9471 < 81920; ++i9471) {
                r310[i9471] = max32(r309[i9471], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r311 */
            for (long i9472 = 0; i9472 < 5120; ++i9472) {
                r311[i9472] = 0;
            }
            for (long i9473 = 0; i9473 < 81920; ++i9473) {
                long t9475 = i9473;
                long c94740 = t9475 / 16384; t9475 %= 16384;
                long c94741 = t9475 / 16384; t9475 %= 16384;
                long c94742 = t9475 / 16; t9475 %= 16;
                long c94743 = t9475;
                r311[c94740 * 1024 + c94741 * 1024 + c94742 * 1] = add32(r311[c94740 * 1024 + c94741 * 1024 + c94742 * 1], r310[i9473]);
            }
            /* neg [neg] -> r312 */
            for (long i9476 = 0; i9476 < 81920; ++i9476) {
                r312[i9476] = neg32(r300[i9476]);
            }
            /* broadcast [broadcast_in_dim] -> r313 */
            for (long i9477 = 0; i9477 < 5120; ++i9477) {
                long t9479 = i9477;
                long c94780 = t9479 / 1024; t9479 %= 1024;
                long c94781 = t9479 / 1024; t9479 %= 1024;
                long c94782 = t9479 / 1; t9479 %= 1;
                long c94783 = t9479;
                r313[i9477] = r307[c94780 * 1024 + c94782 * 1];
            }
            /* sub [sub] -> r314 */
            for (long i9480 = 0; i9480 < 81920; ++i9480) {
                long t9482 = i9480;
                long c94810 = t9482 / 16384; t9482 %= 16384;
                long c94811 = t9482 / 16384; t9482 %= 16384;
                long c94812 = t9482 / 16; t9482 %= 16;
                long c94813 = t9482;
                r314[i9480] = sub32(r312[c94810 * 16384 + c94812 * 16 + c94813 * 1], r313[c94810 * 1024 + c94812 * 1]);
            }
            /* max [max] -> r315 */
            for (long i9483 = 0; i9483 < 81920; ++i9483) {
                r315[i9483] = max32(r314[i9483], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r316 */
            for (long i9484 = 0; i9484 < 5120; ++i9484) {
                r316[i9484] = 0;
            }
            for (long i9485 = 0; i9485 < 81920; ++i9485) {
                long t9487 = i9485;
                long c94860 = t9487 / 16384; t9487 %= 16384;
                long c94861 = t9487 / 16384; t9487 %= 16384;
                long c94862 = t9487 / 16; t9487 %= 16;
                long c94863 = t9487;
                r316[c94860 * 1024 + c94861 * 1024 + c94862 * 1] = add32(r316[c94860 * 1024 + c94861 * 1024 + c94862 * 1], r315[i9485]);
            }
            /* add [add] -> r317 */
            for (long i9488 = 0; i9488 < 5120; ++i9488) {
                r317[i9488] = add32(r311[i9488], r316[i9488]);
            }
            /* gt [gt] -> r318 */
            for (long i9489 = 0; i9489 < 5120; ++i9489) {
                r318[i9489] = r317[i9489] > r301[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r319 */
            for (long i9490 = 0; i9490 < 5120; ++i9490) {
                r319[i9490] = r318[i9490] == 0 ? r303[i9490] : (r307[i9490]);
            }
            /* select_n [select_n] -> r320 */
            for (long i9491 = 0; i9491 < 5120; ++i9491) {
                r320[i9491] = r318[i9491] == 0 ? r307[i9491] : (r304[i9491]);
            }
            memcpy(r302, r305, sizeof(int32_t) * 1);
            memcpy(r303, r319, sizeof(int32_t) * 5120);
            memcpy(r304, r320, sizeof(int32_t) * 5120);
        }
        memcpy(r321, r302, sizeof(int32_t) * 1);
        memcpy(r322, r303, sizeof(int32_t) * 5120);
        memcpy(r323, r304, sizeof(int32_t) * 5120);
        /* sub [sub] -> r324 */
        for (long i9492 = 0; i9492 < 5120; ++i9492) {
            r324[i9492] = sub32(r296[i9492], r323[i9492]);
        }
        memcpy(r325 + t6381 * 5120, r324, sizeof(int32_t) * 5120);
    }
    /* transpose [transpose] -> r326 */
    for (long i9493 = 0; i9493 < 40960; ++i9493) {
        long t9495 = i9493;
        long c94940 = t9495 / 8192; t9495 %= 8192;
        long c94941 = t9495 / 8192; t9495 %= 8192;
        long c94942 = t9495 / 1024; t9495 %= 1024;
        long c94943 = t9495;
        r326[i9493] = r325[c94940 * 1024 + c94941 * 1024 + c94942 * 5120 + c94943 * 1];
    }
    /* reshape [reshape] -> r327 */
    memcpy(r327, r326, sizeof(int32_t) * 40960);
    /* slice [slice] -> r328 */
    for (long i9496 = 0; i9496 < 40000; ++i9496) {
        long t9498 = i9496;
        long c94970 = t9498 / 8000; t9498 %= 8000;
        long c94971 = t9498 / 8000; t9498 %= 8000;
        long c94972 = t9498;
        r328[i9496] = r327[(0 + c94970 * 1) * 8192 + (0 + c94971 * 1) * 8192 + (0 + c94972 * 1) * 1];
    }
    /* transpose [transpose] -> r329 */
    for (long i9499 = 0; i9499 < 40000; ++i9499) {
        long t9501 = i9499;
        long c95000 = t9501 / 40000; t9501 %= 40000;
        long c95001 = t9501 / 8000; t9501 %= 8000;
        long c95002 = t9501;
        r329[i9499] = r328[c95000 * 8000 + c95001 * 8000 + c95002 * 1];
    }
    /* max [max] -> r330 */
    for (long i9502 = 0; i9502 < 40000; ++i9502) {
        r330[i9502] = max32(r329[i9502], r13[0]);
    }
    /* reduce_sum [reduce_sum] -> r331 */
    for (long i9503 = 0; i9503 < 5; ++i9503) {
        r331[i9503] = 0;
    }
    for (long i9504 = 0; i9504 < 40000; ++i9504) {
        long t9506 = i9504;
        long c95050 = t9506 / 40000; t9506 %= 40000;
        long c95051 = t9506 / 8000; t9506 %= 8000;
        long c95052 = t9506;
        r331[c95050 * 5 + c95051 * 1] = add32(r331[c95050 * 5 + c95051 * 1], r330[i9504]);
    }
    /* shl [shift_left] -> r332 */
    for (long i9507 = 0; i9507 < 5; ++i9507) {
        r332[i9507] = shl32(r331[i9507], 1);
    }
    /* shl [shift_left] -> r333 */
    for (long i9508 = 0; i9508 < 8000; ++i9508) {
        r333[i9508] = shl32(r230[i9508], 1);
    }
    /* rev [rev] -> r334 */
    for (long i9509 = 0; i9509 < 6; ++i9509) {
        long t9511 = i9509;
        long c95100 = t9511 / 6; t9511 %= 6;
        long c95101 = t9511;
        r334[i9509] = r2[c95100 * 6 + (6 - 1 - c95101) * 1];
    }
    /* reshape [reshape] -> r335 */
    memcpy(r335, r334, sizeof(int32_t) * 6);
    /* convert [convert_element_type] -> r336 */
    for (long i9512 = 0; i9512 < 1; ++i9512) {
        r336[i9512] = (int32_t)r13[0];
    }
    /* pad [pad] -> r337 */
    for (long i9513 = 0; i9513 < 8005; ++i9513) {
        r337[i9513] = r336[0];
    }
    for (long i9514 = 0; i9514 < 8000; ++i9514) {
        long t9516 = i9514;
        long c95150 = t9516 / 8000; t9516 %= 8000;
        long c95151 = t9516;
        long d9517 = 0 + c95150 * 1;
        long d9518 = 5 + c95151 * 1;
        if (d9517 >= 0 && d9517 < 1 && d9518 >= 0 && d9518 < 8005) r337[d9517 * 8005 + d9518 * 1] = r333[i9514];
    }
    /* convert [convert_element_type] -> r338 */
    for (long i9519 = 0; i9519 < 1; ++i9519) {
        r338[i9519] = (int32_t)r13[0];
    }
    /* pad [pad] -> r339 */
    for (long i9520 = 0; i9520 < 8197; ++i9520) {
        r339[i9520] = r338[0];
    }
    for (long i9521 = 0; i9521 < 8005; ++i9521) {
        long t9523 = i9521;
        long c95220 = t9523 / 8005; t9523 %= 8005;
        long c95221 = t9523;
        long d9524 = 0 + c95220 * 1;
        long d9525 = 0 + c95221 * 1;
        if (d9524 >= 0 && d9524 < 1 && d9525 >= 0 && d9525 < 8197) r339[d9524 * 8197 + d9525 * 1] = r337[i9521];
    }
    /* iota [iota] -> r340 */
    for (long i9526 = 0; i9526 < 1024; ++i9526) {
        long t9528 = i9526;
        long c95270 = t9528;
        r340[i9526] = (int32_t)c95270;
    }
    /* broadcast [broadcast_in_dim] -> r341 */
    for (long i9529 = 0; i9529 < 1024; ++i9529) {
        long t9531 = i9529;
        long c95300 = t9531 / 1; t9531 %= 1;
        long c95301 = t9531;
        r341[i9529] = r340[c95300 * 1];
    }
    /* iota [iota] -> r342 */
    for (long i9532 = 0; i9532 < 6; ++i9532) {
        long t9534 = i9532;
        long c95330 = t9534;
        r342[i9532] = (int32_t)c95330;
    }
    /* broadcast [broadcast_in_dim] -> r343 */
    for (long i9535 = 0; i9535 < 6; ++i9535) {
        long t9537 = i9535;
        long c95360 = t9537 / 6; t9537 %= 6;
        long c95361 = t9537;
        r343[i9535] = r342[c95361 * 1];
    }
    /* add [add] -> r344 */
    for (long i9538 = 0; i9538 < 6144; ++i9538) {
        long t9540 = i9538;
        long c95390 = t9540 / 6; t9540 %= 6;
        long c95391 = t9540;
        r344[i9538] = add32(r341[c95390 * 1], r343[c95391 * 1]);
    }
    /* iota [iota] -> r345 */
    for (long i9541 = 0; i9541 < 8; ++i9541) {
        long t9543 = i9541;
        long c95420 = t9543;
        r345[i9541] = (int32_t)c95420;
    }
    /* shl [mul] -> r346 */
    for (long i9544 = 0; i9544 < 8; ++i9544) {
        r346[i9544] = shl32(r345[i9544], 10);
    }
    /* loop [scan] -> r427 */
    memcpy(r347, r339, sizeof(int32_t) * 8197);
    memcpy(r348, r344, sizeof(int32_t) * 6144);
    memcpy(r349, r335, sizeof(int32_t) * 6);
    for (long t9545 = 0; t9545 < 8; ++t9545) {
        memcpy(r350, r346 + t9545 * 1, sizeof(int32_t) * 1);
        /* lt [lt] -> r351 */
        for (long i10546 = 0; i10546 < 1; ++i10546) {
            r351[i10546] = r350[0] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r353 */
        for (long i10547 = 0; i10547 < 1; ++i10547) {
            r353[i10547] = add32(r350[0], r352[0]);
        }
        /* select_n [select_n] -> r354 */
        for (long i10548 = 0; i10548 < 1; ++i10548) {
            r354[i10548] = r351[0] == 0 ? r350[0] : (r353[0]);
        }
        /* dynamic_slice [dynamic_slice] -> r355 */
        long s10549 = clamp_start((long)r13[0], 1, 1);
        long s10550 = clamp_start((long)r354[0], 8197, 1029);
        {
        for (long i10551 = 0; i10551 < 1029; ++i10551) {
            long t10553 = i10551;
            long c105520 = t10553 / 1029; t10553 %= 1029;
            long c105521 = t10553;
            r355[i10551] = r347[(s10549 + c105520) * 8197 + (s10550 + c105521) * 1];
        }
        }
        /* lt [lt] -> r356 */
        for (long i10554 = 0; i10554 < 6144; ++i10554) {
            r356[i10554] = r348[i10554] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r357 */
        for (long i10555 = 0; i10555 < 6144; ++i10555) {
            r357[i10555] = add32(r348[i10555], r141[0]);
        }
        /* select_n [select_n] -> r358 */
        for (long i10556 = 0; i10556 < 6144; ++i10556) {
            r358[i10556] = r356[i10556] == 0 ? r348[i10556] : (r357[i10556]);
        }
        /* broadcast [broadcast_in_dim] -> r359 */
        for (long i10557 = 0; i10557 < 6144; ++i10557) {
            long t10559 = i10557;
            long c105580 = t10559 / 6; t10559 %= 6;
            long c105581 = t10559 / 1; t10559 %= 1;
            long c105582 = t10559;
            r359[i10557] = r358[c105580 * 6 + c105581 * 1];
        }
        /* gather [gather] -> r360 */
        for (long i10560 = 0; i10560 < 6144; ++i10560) {
            long t10562 = i10560;
            long c105610 = t10562 / 6144; t10562 %= 6144;
            long c105611 = t10562 / 6; t10562 %= 6;
            long c105612 = t10562;
            long row10563 = c105611 * 6 + c105612 * 1;
            long s10564 = clamp_start((long)r359[row10563 + 0], 1029, 1);
            r360[i10560] = r355[c105610 * 1029 + s10564 * 1];
        }
        /* broadcast [broadcast_in_dim] -> r361 */
        for (long i10565 = 0; i10565 < 6144; ++i10565) {
            long t10567 = i10565;
            long c105660 = t10567 / 6144; t10567 %= 6144;
            long c105661 = t10567 / 6144; t10567 %= 6144;
            long c105662 = t10567 / 6; t10567 %= 6;
            long c105663 = t10567;
            r361[i10565] = r360[c105662 * 6 + c105663 * 1];
        }
        /* add [add] -> r362 */
        for (long i10568 = 0; i10568 < 6144; ++i10568) {
            long t10570 = i10568;
            long c105690 = t10570 / 6144; t10570 %= 6144;
            long c105691 = t10570 / 6144; t10570 %= 6144;
            long c105692 = t10570 / 6; t10570 %= 6;
            long c105693 = t10570;
            r362[i10568] = add32(r349[c105693 * 1], r361[c105692 * 6 + c105693 * 1]);
        }
        /* convert [convert_element_type] -> r363 */
        for (long i10571 = 0; i10571 < 1; ++i10571) {
            r363[i10571] = (int32_t)r42[0];
        }
        /* max [max] -> r364 */
        for (long i10572 = 0; i10572 < 6144; ++i10572) {
            r364[i10572] = max32(r363[0], r362[i10572]);
        }
        /* convert [convert_element_type] -> r365 */
        for (long i10573 = 0; i10573 < 1; ++i10573) {
            r365[i10573] = (int32_t)r43[0];
        }
        /* min [min] -> r366 */
        for (long i10574 = 0; i10574 < 6144; ++i10574) {
            r366[i10574] = min32(r365[0], r364[i10574]);
        }
        /* sub [sub] -> r367 */
        for (long i10575 = 0; i10575 < 6144; ++i10575) {
            long t10577 = i10575;
            long c105760 = t10577 / 6144; t10577 %= 6144;
            long c105761 = t10577 / 6144; t10577 %= 6144;
            long c105762 = t10577 / 6; t10577 %= 6;
            long c105763 = t10577;
            r367[i10575] = sub32(r349[c105763 * 1], r361[c105762 * 6 + c105763 * 1]);
        }
        /* convert [convert_element_type] -> r368 */
        for (long i10578 = 0; i10578 < 1; ++i10578) {
            r368[i10578] = (int32_t)r42[0];
        }
        /* max [max] -> r369 */
        for (long i10579 = 0; i10579 < 6144; ++i10579) {
            r369[i10579] = max32(r368[0], r367[i10579]);
        }
        /* convert [convert_element_type] -> r370 */
        for (long i10580 = 0; i10580 < 1; ++i10580) {
            r370[i10580] = (int32_t)r43[0];
        }
        /* min [min] -> r371 */
        for (long i10581 = 0; i10581 < 6144; ++i10581) {
            r371[i10581] = min32(r370[0], r369[i10581]);
        }
        /* abs [abs] -> r372 */
        for (long i10582 = 0; i10582 < 6144; ++i10582) {
            r372[i10582] = abs32(r366[i10582]);
        }
        /* reduce_max [reduce_max] -> r373 */
        for (long i10583 = 0; i10583 < 1024; ++i10583) {
            r373[i10583] = (-2147483647 - 1);
        }
        for (long i10584 = 0; i10584 < 6144; ++i10584) {
            long t10586 = i10584;
            long c105850 = t10586 / 6144; t10586 %= 6144;
            long c105851 = t10586 / 6144; t10586 %= 6144;
            long c105852 = t10586 / 6; t10586 %= 6;
            long c105853 = t10586;
            r373[c105850 * 1024 + c105851 * 1024 + c105852 * 1] = max32(r373[c105850 * 1024 + c105851 * 1024 + c105852 * 1], r372[i10584]);
        }
        /* sub [sub] -> r374 */
        for (long i10587 = 0; i10587 < 1024; ++i10587) {
            r374[i10587] = sub32(r373[i10587], r55[0]);
        }
        /* loop [scan] -> r396 */
        memcpy(r375, r366, sizeof(int32_t) * 6144);
        memcpy(r376, r55, sizeof(int32_t) * 1);
        memcpy(r377, r13, sizeof(int32_t) * 1);
        memcpy(r378, r374, sizeof(int32_t) * 1024);
        memcpy(r379, r373, sizeof(int32_t) * 1024);
        for (long t10588 = 0; t10588 < 12; ++t10588) {
            /* add [add] -> r380 */
            for (long i11589 = 0; i11589 < 1; ++i11589) {
                r380[i11589] = add32(r377[0], r9[0]);
            }
            /* add [add] -> r381 */
            for (long i11590 = 0; i11590 < 1024; ++i11590) {
                r381[i11590] = add32(r378[i11590], r379[i11590]);
            }
            /* shra [shift_right_arithmetic] -> r382 */
            for (long i11591 = 0; i11591 < 1024; ++i11591) {
                r382[i11591] = asr32(r381[i11591], 1);
            }
            /* broadcast [broadcast_in_dim] -> r383 */
            for (long i11592 = 0; i11592 < 1024; ++i11592) {
                long t11594 = i11592;
                long c115930 = t11594 / 1024; t11594 %= 1024;
                long c115931 = t11594 / 1024; t11594 %= 1024;
                long c115932 = t11594 / 1; t11594 %= 1;
                long c115933 = t11594;
                r383[i11592] = r382[c115932 * 1];
            }
            /* sub [sub] -> r384 */
            for (long i11595 = 0; i11595 < 6144; ++i11595) {
                long t11597 = i11595;
                long c115960 = t11597 / 6144; t11597 %= 6144;
                long c115961 = t11597 / 6144; t11597 %= 6144;
                long c115962 = t11597 / 6; t11597 %= 6;
                long c115963 = t11597;
                r384[i11595] = sub32(r375[c115962 * 6 + c115963 * 1], r383[c115962 * 1]);
            }
            /* max [max] -> r385 */
            for (long i11598 = 0; i11598 < 6144; ++i11598) {
                r385[i11598] = max32(r384[i11598], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r386 */
            for (long i11599 = 0; i11599 < 1024; ++i11599) {
                r386[i11599] = 0;
            }
            for (long i11600 = 0; i11600 < 6144; ++i11600) {
                long t11602 = i11600;
                long c116010 = t11602 / 6144; t11602 %= 6144;
                long c116011 = t11602 / 6144; t11602 %= 6144;
                long c116012 = t11602 / 6; t11602 %= 6;
                long c116013 = t11602;
                r386[c116010 * 1024 + c116011 * 1024 + c116012 * 1] = add32(r386[c116010 * 1024 + c116011 * 1024 + c116012 * 1], r385[i11600]);
            }
            /* neg [neg] -> r387 */
            for (long i11603 = 0; i11603 < 6144; ++i11603) {
                r387[i11603] = neg32(r375[i11603]);
            }
            /* broadcast [broadcast_in_dim] -> r388 */
            for (long i11604 = 0; i11604 < 1024; ++i11604) {
                long t11606 = i11604;
                long c116050 = t11606 / 1024; t11606 %= 1024;
                long c116051 = t11606 / 1024; t11606 %= 1024;
                long c116052 = t11606 / 1; t11606 %= 1;
                long c116053 = t11606;
                r388[i11604] = r382[c116052 * 1];
            }
            /* sub [sub] -> r389 */
            for (long i11607 = 0; i11607 < 6144; ++i11607) {
                long t11609 = i11607;
                long c116080 = t11609 / 6144; t11609 %= 6144;
                long c116081 = t11609 / 6144; t11609 %= 6144;
                long c116082 = t11609 / 6; t11609 %= 6;
                long c116083 = t11609;
                r389[i11607] = sub32(r387[c116082 * 6 + c116083 * 1], r388[c116082 * 1]);
            }
            /* max [max] -> r390 */
            for (long i11610 = 0; i11610 < 6144; ++i11610) {
                r390[i11610] = max32(r389[i11610], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r391 */
            for (long i11611 = 0; i11611 < 1024; ++i11611) {
                r391[i11611] = 0;
            }
            for (long i11612 = 0; i11612 < 6144; ++i11612) {
                long t11614 = i11612;
                long c116130 = t11614 / 6144; t11614 %= 6144;
                long c116131 = t11614 / 6144; t11614 %= 6144;
                long c116132 = t11614 / 6; t11614 %= 6;
                long c116133 = t11614;
                r391[c116130 * 1024 + c116131 * 1024 + c116132 * 1] = add32(r391[c116130 * 1024 + c116131 * 1024 + c116132 * 1], r390[i11612]);
            }
            /* add [add] -> r392 */
            for (long i11615 = 0; i11615 < 1024; ++i11615) {
                r392[i11615] = add32(r386[i11615], r391[i11615]);
            }
            /* gt [gt] -> r393 */
            for (long i11616 = 0; i11616 < 1024; ++i11616) {
                r393[i11616] = r392[i11616] > r376[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r394 */
            for (long i11617 = 0; i11617 < 1024; ++i11617) {
                r394[i11617] = r393[i11617] == 0 ? r378[i11617] : (r382[i11617]);
            }
            /* select_n [select_n] -> r395 */
            for (long i11618 = 0; i11618 < 1024; ++i11618) {
                r395[i11618] = r393[i11618] == 0 ? r382[i11618] : (r379[i11618]);
            }
            memcpy(r377, r380, sizeof(int32_t) * 1);
            memcpy(r378, r394, sizeof(int32_t) * 1024);
            memcpy(r379, r395, sizeof(int32_t) * 1024);
        }
        memcpy(r396, r377, sizeof(int32_t) * 1);
        memcpy(r397, r378, sizeof(int32_t) * 1024);
        memcpy(r398, r379, sizeof(int32_t) * 1024);
        /* abs [abs] -> r399 */
        for (long i11619 = 0; i11619 < 6144; ++i11619) {
            r399[i11619] = abs32(r371[i11619]);
        }
        /* reduce_max [reduce_max] -> r400 */
        for (long i11620 = 0; i11620 < 1024; ++i11620) {
            r400[i11620] = (-2147483647 - 1);
        }
        for (long i11621 = 0; i11621 < 6144; ++i11621) {
            long t11623 = i11621;
            long c116220 = t11623 / 6144; t11623 %= 6144;
            long c116221 = t11623 / 6144; t11623 %= 6144;
            long c116222 = t11623 / 6; t11623 %= 6;
            long c116223 = t11623;
            r400[c116220 * 1024 + c116221 * 1024 + c116222 * 1] = max32(r400[c116220 * 1024 + c116221 * 1024 + c116222 * 1], r399[i11621]);
        }
        /* sub [sub] -> r401 */
        for (long i11624 = 0; i11624 < 1024; ++i11624) {
            r401[i11624] = sub32(r400[i11624], r55[0]);
        }
        /* loop [scan] -> r423 */
        memcpy(r402, r371, sizeof(int32_t) * 6144);
        memcpy(r403, r55, sizeof(int32_t) * 1);
        memcpy(r404, r13, sizeof(int32_t) * 1);
        memcpy(r405, r401, sizeof(int32_t) * 1024);
        memcpy(r406, r400, sizeof(int32_t) * 1024);
        for (long t11625 = 0; t11625 < 12; ++t11625) {
            /* add [add] -> r407 */
            for (long i12626 = 0; i12626 < 1; ++i12626) {
                r407[i12626] = add32(r404[0], r9[0]);
            }
            /* add [add] -> r408 */
            for (long i12627 = 0; i12627 < 1024; ++i12627) {
                r408[i12627] = add32(r405[i12627], r406[i12627]);
            }
            /* shra [shift_right_arithmetic] -> r409 */
            for (long i12628 = 0; i12628 < 1024; ++i12628) {
                r409[i12628] = asr32(r408[i12628], 1);
            }
            /* broadcast [broadcast_in_dim] -> r410 */
            for (long i12629 = 0; i12629 < 1024; ++i12629) {
                long t12631 = i12629;
                long c126300 = t12631 / 1024; t12631 %= 1024;
                long c126301 = t12631 / 1024; t12631 %= 1024;
                long c126302 = t12631 / 1; t12631 %= 1;
                long c126303 = t12631;
                r410[i12629] = r409[c126302 * 1];
            }
            /* sub [sub] -> r411 */
            for (long i12632 = 0; i12632 < 6144; ++i12632) {
                long t12634 = i12632;
                long c126330 = t12634 / 6144; t12634 %= 6144;
                long c126331 = t12634 / 6144; t12634 %= 6144;
                long c126332 = t12634 / 6; t12634 %= 6;
                long c126333 = t12634;
                r411[i12632] = sub32(r402[c126332 * 6 + c126333 * 1], r410[c126332 * 1]);
            }
            /* max [max] -> r412 */
            for (long i12635 = 0; i12635 < 6144; ++i12635) {
                r412[i12635] = max32(r411[i12635], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r413 */
            for (long i12636 = 0; i12636 < 1024; ++i12636) {
                r413[i12636] = 0;
            }
            for (long i12637 = 0; i12637 < 6144; ++i12637) {
                long t12639 = i12637;
                long c126380 = t12639 / 6144; t12639 %= 6144;
                long c126381 = t12639 / 6144; t12639 %= 6144;
                long c126382 = t12639 / 6; t12639 %= 6;
                long c126383 = t12639;
                r413[c126380 * 1024 + c126381 * 1024 + c126382 * 1] = add32(r413[c126380 * 1024 + c126381 * 1024 + c126382 * 1], r412[i12637]);
            }
            /* neg [neg] -> r414 */
            for (long i12640 = 0; i12640 < 6144; ++i12640) {
                r414[i12640] = neg32(r402[i12640]);
            }
            /* broadcast [broadcast_in_dim] -> r415 */
            for (long i12641 = 0; i12641 < 1024; ++i12641) {
                long t12643 = i12641;
                long c126420 = t12643 / 1024; t12643 %= 1024;
                long c126421 = t12643 / 1024; t12643 %= 1024;
                long c126422 = t12643 / 1; t12643 %= 1;
                long c126423 = t12643;
                r415[i12641] = r409[c126422 * 1];
            }
            /* sub [sub] -> r416 */
            for (long i12644 = 0; i12644 < 6144; ++i12644) {
                long t12646 = i12644;
                long c126450 = t12646 / 6144; t12646 %= 6144;
                long c126451 = t12646 / 6144; t12646 %= 6144;
                long c126452 = t12646 / 6; t12646 %= 6;
                long c126453 = t12646;
                r416[i12644] = sub32(r414[c126452 * 6 + c126453 * 1], r415[c126452 * 1]);
            }
            /* max [max] -> r417 */
            for (long i12647 = 0; i12647 < 6144; ++i12647) {
                r417[i12647] = max32(r416[i12647], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r418 */
            for (long i12648 = 0; i12648 < 1024; ++i12648) {
                r418[i12648] = 0;
            }
            for (long i12649 = 0; i12649 < 6144; ++i12649) {
                long t12651 = i12649;
                long c126500 = t12651 / 6144; t12651 %= 6144;
                long c126501 = t12651 / 6144; t12651 %= 6144;
                long c126502 = t12651 / 6; t12651 %= 6;
                long c126503 = t12651;
                r418[c126500 * 1024 + c126501 * 1024 + c126502 * 1] = add32(r418[c126500 * 1024 + c126501 * 1024 + c126502 * 1], r417[i12649]);
            }
            /* add [add] -> r419 */
            for (long i12652 = 0; i12652 < 1024; ++i12652) {
                r419[i12652] = add32(r413[i12652], r418[i12652]);
            }
            /* gt [gt] -> r420 */
            for (long i12653 = 0; i12653 < 1024; ++i12653) {
                r420[i12653] = r419[i12653] > r403[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r421 */
            for (long i12654 = 0; i12654 < 1024; ++i12654) {
                r421[i12654] = r420[i12654] == 0 ? r405[i12654] : (r409[i12654]);
            }
            /* select_n [select_n] -> r422 */
            for (long i12655 = 0; i12655 < 1024; ++i12655) {
                r422[i12655] = r420[i12655] == 0 ? r409[i12655] : (r406[i12655]);
            }
            memcpy(r404, r407, sizeof(int32_t) * 1);
            memcpy(r405, r421, sizeof(int32_t) * 1024);
            memcpy(r406, r422, sizeof(int32_t) * 1024);
        }
        memcpy(r423, r404, sizeof(int32_t) * 1);
        memcpy(r424, r405, sizeof(int32_t) * 1024);
        memcpy(r425, r406, sizeof(int32_t) * 1024);
        /* sub [sub] -> r426 */
        for (long i12656 = 0; i12656 < 1024; ++i12656) {
            r426[i12656] = sub32(r398[i12656], r425[i12656]);
        }
        memcpy(r427 + t9545 * 1024, r426, sizeof(int32_t) * 1024);
    }
    /* transpose [transpose] -> r428 */
    for (long i12657 = 0; i12657 < 8192; ++i12657) {
        long t12659 = i12657;
        long c126580 = t12659 / 8192; t12659 %= 8192;
        long c126581 = t12659 / 8192; t12659 %= 8192;
        long c126582 = t12659 / 1024; t12659 %= 1024;
        long c126583 = t12659;
        r428[i12657] = r427[c126580 * 1024 + c126581 * 1024 + c126582 * 1024 + c126583 * 1];
    }
    /* reshape [reshape] -> r429 */
    memcpy(r429, r428, sizeof(int32_t) * 8192);
    /* slice [slice] -> r430 */
    for (long i12660 = 0; i12660 < 8000; ++i12660) {
        long t12662 = i12660;
        long c126610 = t12662 / 8000; t12662 %= 8000;
        long c126611 = t12662 / 8000; t12662 %= 8000;
        long c126612 = t12662;
        r430[i12660] = r429[(0 + c126610 * 1) * 8192 + (0 + c126611 * 1) * 8192 + (0 + c126612 * 1) * 1];
    }
    /* transpose [transpose] -> r431 */
    for (long i12663 = 0; i12663 < 8000; ++i12663) {
        long t12665 = i12663;
        long c126640 = t12665 / 8000; t12665 %= 8000;
        long c126641 = t12665 / 8000; t12665 %= 8000;
        long c126642 = t12665;
        r431[i12663] = r430[c126640 * 8000 + c126641 * 8000 + c126642 * 1];
    }
    /* slice [slice] -> r432 */
    for (long i12666 = 0; i12666 < 8000; ++i12666) {
        long t12668 = i12666;
        long c126670 = t12668 / 8000; t12668 %= 8000;
        long c126671 = t12668 / 8000; t12668 %= 8000;
        long c126672 = t12668;
        r432[i12666] = r431[(0 + c126670 * 1) * 8000 + (0 + c126671 * 1) * 8000 + (0 + c126672 * 1) * 1];
    }
    /* reshape [squeeze] -> r433 */
    memcpy(r433, r432, sizeof(int32_t) * 8000);
    /* shra [shift_right_arithmetic] -> r434 */
    for (long i12669 = 0; i12669 < 8000; ++i12669) {
        r434[i12669] = asr32(r433[i12669], 1);
    }
    /* convert [convert_element_type] -> r435 */
    for (long i12670 = 0; i12670 < 1; ++i12670) {
        r435[i12670] = (int32_t)r220[0];
    }
    /* max [max] -> r436 */
    for (long i12671 = 0; i12671 < 8000; ++i12671) {
        r436[i12671] = max32(r435[0], r434[i12671]);
    }
    /* convert [convert_element_type] -> r437 */
    for (long i12672 = 0; i12672 < 1; ++i12672) {
        r437[i12672] = (int32_t)r221[0];
    }
    /* min [min] -> r438 */
    for (long i12673 = 0; i12673 < 8000; ++i12673) {
        r438[i12673] = min32(r437[0], r436[i12673]);
    }
    /* iota [iota] -> r439 */
    for (long i12674 = 0; i12674 < 4000; ++i12674) {
        long t12676 = i12674;
        long c126750 = t12676;
        r439[i12674] = (int32_t)c126750;
    }
    /* shl [mul] -> r440 */
    for (long i12677 = 0; i12677 < 4000; ++i12677) {
        r440[i12677] = shl32(r439[i12677], 1);
    }
    /* add [add] -> r441 */
    for (long i12678 = 0; i12678 < 4000; ++i12678) {
        r441[i12678] = add32(r13[0], r440[i12678]);
    }
    /* broadcast [broadcast_in_dim] -> r442 */
    for (long i12679 = 0; i12679 < 4000; ++i12679) {
        long t12681 = i12679;
        long c126800 = t12681 / 1; t12681 %= 1;
        long c126801 = t12681;
        r442[i12679] = r441[c126800 * 1];
    }
    /* gather [gather] -> r443 */
    for (long i12682 = 0; i12682 < 4000; ++i12682) {
        long t12684 = i12682;
        long c126830 = t12684 / 4000; t12684 %= 4000;
        long c126831 = t12684;
        long row12685 = c126831 * 1;
        long s12686 = clamp_start((long)r442[row12685 + 0], 8000, 1);
        r443[i12682] = r438[c126830 * 8000 + s12686 * 1];
    }
    /* shl [shift_left] -> r444 */
    for (long i12687 = 0; i12687 < 4000; ++i12687) {
        r444[i12687] = shl32(r443[i12687], 1);
    }
    /* rev [rev] -> r445 */
    for (long i12688 = 0; i12688 < 80; ++i12688) {
        long t12690 = i12688;
        long c126890 = t12690 / 16; t12690 %= 16;
        long c126891 = t12690;
        r445[i12688] = r1[c126890 * 16 + (16 - 1 - c126891) * 1];
    }
    /* reshape [reshape] -> r446 */
    memcpy(r446, r445, sizeof(int32_t) * 80);
    /* convert [convert_element_type] -> r447 */
    for (long i12691 = 0; i12691 < 1; ++i12691) {
        r447[i12691] = (int32_t)r13[0];
    }
    /* pad [pad] -> r448 */
    for (long i12692 = 0; i12692 < 4015; ++i12692) {
        r448[i12692] = r447[0];
    }
    for (long i12693 = 0; i12693 < 4000; ++i12693) {
        long t12695 = i12693;
        long c126940 = t12695 / 4000; t12695 %= 4000;
        long c126941 = t12695;
        long d12696 = 0 + c126940 * 1;
        long d12697 = 15 + c126941 * 1;
        if (d12696 >= 0 && d12696 < 1 && d12697 >= 0 && d12697 < 4015) r448[d12696 * 4015 + d12697 * 1] = r444[i12693];
    }
    /* convert [convert_element_type] -> r449 */
    for (long i12698 = 0; i12698 < 1; ++i12698) {
        r449[i12698] = (int32_t)r13[0];
    }
    /* pad [pad] -> r450 */
    for (long i12699 = 0; i12699 < 4111; ++i12699) {
        r450[i12699] = r449[0];
    }
    for (long i12700 = 0; i12700 < 4015; ++i12700) {
        long t12702 = i12700;
        long c127010 = t12702 / 4015; t12702 %= 4015;
        long c127011 = t12702;
        long d12703 = 0 + c127010 * 1;
        long d12704 = 0 + c127011 * 1;
        if (d12703 >= 0 && d12703 < 1 && d12704 >= 0 && d12704 < 4111) r450[d12703 * 4111 + d12704 * 1] = r448[i12700];
    }
    /* iota [iota] -> r451 */
    for (long i12705 = 0; i12705 < 1024; ++i12705) {
        long t12707 = i12705;
        long c127060 = t12707;
        r451[i12705] = (int32_t)c127060;
    }
    /* broadcast [broadcast_in_dim] -> r452 */
    for (long i12708 = 0; i12708 < 1024; ++i12708) {
        long t12710 = i12708;
        long c127090 = t12710 / 1; t12710 %= 1;
        long c127091 = t12710;
        r452[i12708] = r451[c127090 * 1];
    }
    /* iota [iota] -> r453 */
    for (long i12711 = 0; i12711 < 16; ++i12711) {
        long t12713 = i12711;
        long c127120 = t12713;
        r453[i12711] = (int32_t)c127120;
    }
    /* broadcast [broadcast_in_dim] -> r454 */
    for (long i12714 = 0; i12714 < 16; ++i12714) {
        long t12716 = i12714;
        long c127150 = t12716 / 16; t12716 %= 16;
        long c127151 = t12716;
        r454[i12714] = r453[c127151 * 1];
    }
    /* add [add] -> r455 */
    for (long i12717 = 0; i12717 < 16384; ++i12717) {
        long t12719 = i12717;
        long c127180 = t12719 / 16; t12719 %= 16;
        long c127181 = t12719;
        r455[i12717] = add32(r452[c127180 * 1], r454[c127181 * 1]);
    }
    /* iota [iota] -> r456 */
    for (long i12720 = 0; i12720 < 4; ++i12720) {
        long t12722 = i12720;
        long c127210 = t12722;
        r456[i12720] = (int32_t)c127210;
    }
    /* shl [mul] -> r457 */
    for (long i12723 = 0; i12723 < 4; ++i12723) {
        r457[i12723] = shl32(r456[i12723], 10);
    }
    /* loop [scan] -> r538 */
    memcpy(r458, r450, sizeof(int32_t) * 4111);
    memcpy(r459, r455, sizeof(int32_t) * 16384);
    memcpy(r460, r446, sizeof(int32_t) * 80);
    for (long t12724 = 0; t12724 < 4; ++t12724) {
        memcpy(r461, r457 + t12724 * 1, sizeof(int32_t) * 1);
        /* lt [lt] -> r462 */
        for (long i13725 = 0; i13725 < 1; ++i13725) {
            r462[i13725] = r461[0] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r464 */
        for (long i13726 = 0; i13726 < 1; ++i13726) {
            r464[i13726] = add32(r461[0], r463[0]);
        }
        /* select_n [select_n] -> r465 */
        for (long i13727 = 0; i13727 < 1; ++i13727) {
            r465[i13727] = r462[0] == 0 ? r461[0] : (r464[0]);
        }
        /* dynamic_slice [dynamic_slice] -> r466 */
        long s13728 = clamp_start((long)r13[0], 1, 1);
        long s13729 = clamp_start((long)r465[0], 4111, 1039);
        {
        for (long i13730 = 0; i13730 < 1039; ++i13730) {
            long t13732 = i13730;
            long c137310 = t13732 / 1039; t13732 %= 1039;
            long c137311 = t13732;
            r466[i13730] = r458[(s13728 + c137310) * 4111 + (s13729 + c137311) * 1];
        }
        }
        /* lt [lt] -> r467 */
        for (long i13733 = 0; i13733 < 16384; ++i13733) {
            r467[i13733] = r459[i13733] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r468 */
        for (long i13734 = 0; i13734 < 16384; ++i13734) {
            r468[i13734] = add32(r459[i13734], r35[0]);
        }
        /* select_n [select_n] -> r469 */
        for (long i13735 = 0; i13735 < 16384; ++i13735) {
            r469[i13735] = r467[i13735] == 0 ? r459[i13735] : (r468[i13735]);
        }
        /* broadcast [broadcast_in_dim] -> r470 */
        for (long i13736 = 0; i13736 < 16384; ++i13736) {
            long t13738 = i13736;
            long c137370 = t13738 / 16; t13738 %= 16;
            long c137371 = t13738 / 1; t13738 %= 1;
            long c137372 = t13738;
            r470[i13736] = r469[c137370 * 16 + c137371 * 1];
        }
        /* gather [gather] -> r471 */
        for (long i13739 = 0; i13739 < 16384; ++i13739) {
            long t13741 = i13739;
            long c137400 = t13741 / 16384; t13741 %= 16384;
            long c137401 = t13741 / 16; t13741 %= 16;
            long c137402 = t13741;
            long row13742 = c137401 * 16 + c137402 * 1;
            long s13743 = clamp_start((long)r470[row13742 + 0], 1039, 1);
            r471[i13739] = r466[c137400 * 1039 + s13743 * 1];
        }
        /* broadcast [broadcast_in_dim] -> r472 */
        for (long i13744 = 0; i13744 < 16384; ++i13744) {
            long t13746 = i13744;
            long c137450 = t13746 / 16384; t13746 %= 16384;
            long c137451 = t13746 / 16384; t13746 %= 16384;
            long c137452 = t13746 / 16; t13746 %= 16;
            long c137453 = t13746;
            r472[i13744] = r471[c137452 * 16 + c137453 * 1];
        }
        /* add [add] -> r473 */
        for (long i13747 = 0; i13747 < 81920; ++i13747) {
            long t13749 = i13747;
            long c137480 = t13749 / 16384; t13749 %= 16384;
            long c137481 = t13749 / 16384; t13749 %= 16384;
            long c137482 = t13749 / 16; t13749 %= 16;
            long c137483 = t13749;
            r473[i13747] = add32(r460[c137480 * 16 + c137483 * 1], r472[c137482 * 16 + c137483 * 1]);
        }
        /* convert [convert_element_type] -> r474 */
        for (long i13750 = 0; i13750 < 1; ++i13750) {
            r474[i13750] = (int32_t)r42[0];
        }
        /* max [max] -> r475 */
        for (long i13751 = 0; i13751 < 81920; ++i13751) {
            r475[i13751] = max32(r474[0], r473[i13751]);
        }
        /* convert [convert_element_type] -> r476 */
        for (long i13752 = 0; i13752 < 1; ++i13752) {
            r476[i13752] = (int32_t)r43[0];
        }
        /* min [min] -> r477 */
        for (long i13753 = 0; i13753 < 81920; ++i13753) {
            r477[i13753] = min32(r476[0], r475[i13753]);
        }
        /* sub [sub] -> r478 */
        for (long i13754 = 0; i13754 < 81920; ++i13754) {
            long t13756 = i13754;
            long c137550 = t13756 / 16384; t13756 %= 16384;
            long c137551 = t13756 / 16384; t13756 %= 16384;
            long c137552 = t13756 / 16; t13756 %= 16;
            long c137553 = t13756;
            r478[i13754] = sub32(r460[c137550 * 16 + c137553 * 1], r472[c137552 * 16 + c137553 * 1]);
        }
        /* convert [convert_element_type] -> r479 */
        for (long i13757 = 0; i13757 < 1; ++i13757) {
            r479[i13757] = (int32_t)r42[0];
        }
        /* max [max] -> r480 */
        for (long i13758 = 0; i13758 < 81920; ++i13758) {
            r480[i13758] = max32(r479[0], r478[i13758]);
        }
        /* convert [convert_element_type] -> r481 */
        for (long i13759 = 0; i13759 < 1; ++i13759) {
            r481[i13759] = (int32_t)r43[0];
        }
        /* min [min] -> r482 */
        for (long i13760 = 0; i13760 < 81920; ++i13760) {
            r482[i13760] = min32(r481[0], r480[i13760]);
        }
        /* abs [abs] -> r483 */
        for (long i13761 = 0; i13761 < 81920; ++i13761) {
            r483[i13761] = abs32(r477[i13761]);
        }
        /* reduce_max [reduce_max] -> r484 */
        for (long i13762 = 0; i13762 < 5120; ++i13762) {
            r484[i13762] = (-2147483647 - 1);
        }
        for (long i13763 = 0; i13763 < 81920; ++i13763) {
            long t13765 = i13763;
            long c137640 = t13765 / 16384; t13765 %= 16384;
            long c137641 = t13765 / 16384; t13765 %= 16384;
            long c137642 = t13765 / 16; t13765 %= 16;
            long c137643 = t13765;
            r484[c137640 * 1024 + c137641 * 1024 + c137642 * 1] = max32(r484[c137640 * 1024 + c137641 * 1024 + c137642 * 1], r483[i13763]);
        }
        /* sub [sub] -> r485 */
        for (long i13766 = 0; i13766 < 5120; ++i13766) {
            r485[i13766] = sub32(r484[i13766], r55[0]);
        }
        /* loop [scan] -> r507 */
        memcpy(r486, r477, sizeof(int32_t) * 81920);
        memcpy(r487, r55, sizeof(int32_t) * 1);
        memcpy(r488, r13, sizeof(int32_t) * 1);
        memcpy(r489, r485, sizeof(int32_t) * 5120);
        memcpy(r490, r484, sizeof(int32_t) * 5120);
        for (long t13767 = 0; t13767 < 12; ++t13767) {
            /* add [add] -> r491 */
            for (long i14768 = 0; i14768 < 1; ++i14768) {
                r491[i14768] = add32(r488[0], r9[0]);
            }
            /* add [add] -> r492 */
            for (long i14769 = 0; i14769 < 5120; ++i14769) {
                r492[i14769] = add32(r489[i14769], r490[i14769]);
            }
            /* shra [shift_right_arithmetic] -> r493 */
            for (long i14770 = 0; i14770 < 5120; ++i14770) {
                r493[i14770] = asr32(r492[i14770], 1);
            }
            /* broadcast [broadcast_in_dim] -> r494 */
            for (long i14771 = 0; i14771 < 5120; ++i14771) {
                long t14773 = i14771;
                long c147720 = t14773 / 1024; t14773 %= 1024;
                long c147721 = t14773 / 1024; t14773 %= 1024;
                long c147722 = t14773 / 1; t14773 %= 1;
                long c147723 = t14773;
                r494[i14771] = r493[c147720 * 1024 + c147722 * 1];
            }
            /* sub [sub] -> r495 */
            for (long i14774 = 0; i14774 < 81920; ++i14774) {
                long t14776 = i14774;
                long c147750 = t14776 / 16384; t14776 %= 16384;
                long c147751 = t14776 / 16384; t14776 %= 16384;
                long c147752 = t14776 / 16; t14776 %= 16;
                long c147753 = t14776;
                r495[i14774] = sub32(r486[c147750 * 16384 + c147752 * 16 + c147753 * 1], r494[c147750 * 1024 + c147752 * 1]);
            }
            /* max [max] -> r496 */
            for (long i14777 = 0; i14777 < 81920; ++i14777) {
                r496[i14777] = max32(r495[i14777], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r497 */
            for (long i14778 = 0; i14778 < 5120; ++i14778) {
                r497[i14778] = 0;
            }
            for (long i14779 = 0; i14779 < 81920; ++i14779) {
                long t14781 = i14779;
                long c147800 = t14781 / 16384; t14781 %= 16384;
                long c147801 = t14781 / 16384; t14781 %= 16384;
                long c147802 = t14781 / 16; t14781 %= 16;
                long c147803 = t14781;
                r497[c147800 * 1024 + c147801 * 1024 + c147802 * 1] = add32(r497[c147800 * 1024 + c147801 * 1024 + c147802 * 1], r496[i14779]);
            }
            /* neg [neg] -> r498 */
            for (long i14782 = 0; i14782 < 81920; ++i14782) {
                r498[i14782] = neg32(r486[i14782]);
            }
            /* broadcast [broadcast_in_dim] -> r499 */
            for (long i14783 = 0; i14783 < 5120; ++i14783) {
                long t14785 = i14783;
                long c147840 = t14785 / 1024; t14785 %= 1024;
                long c147841 = t14785 / 1024; t14785 %= 1024;
                long c147842 = t14785 / 1; t14785 %= 1;
                long c147843 = t14785;
                r499[i14783] = r493[c147840 * 1024 + c147842 * 1];
            }
            /* sub [sub] -> r500 */
            for (long i14786 = 0; i14786 < 81920; ++i14786) {
                long t14788 = i14786;
                long c147870 = t14788 / 16384; t14788 %= 16384;
                long c147871 = t14788 / 16384; t14788 %= 16384;
                long c147872 = t14788 / 16; t14788 %= 16;
                long c147873 = t14788;
                r500[i14786] = sub32(r498[c147870 * 16384 + c147872 * 16 + c147873 * 1], r499[c147870 * 1024 + c147872 * 1]);
            }
            /* max [max] -> r501 */
            for (long i14789 = 0; i14789 < 81920; ++i14789) {
                r501[i14789] = max32(r500[i14789], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r502 */
            for (long i14790 = 0; i14790 < 5120; ++i14790) {
                r502[i14790] = 0;
            }
            for (long i14791 = 0; i14791 < 81920; ++i14791) {
                long t14793 = i14791;
                long c147920 = t14793 / 16384; t14793 %= 16384;
                long c147921 = t14793 / 16384; t14793 %= 16384;
                long c147922 = t14793 / 16; t14793 %= 16;
                long c147923 = t14793;
                r502[c147920 * 1024 + c147921 * 1024 + c147922 * 1] = add32(r502[c147920 * 1024 + c147921 * 1024 + c147922 * 1], r501[i14791]);
            }
            /* add [add] -> r503 */
            for (long i14794 = 0; i14794 < 5120; ++i14794) {
                r503[i14794] = add32(r497[i14794], r502[i14794]);
            }
            /* gt [gt] -> r504 */
            for (long i14795 = 0; i14795 < 5120; ++i14795) {
                r504[i14795] = r503[i14795] > r487[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r505 */
            for (long i14796 = 0; i14796 < 5120; ++i14796) {
                r505[i14796] = r504[i14796] == 0 ? r489[i14796] : (r493[i14796]);
            }
            /* select_n [select_n] -> r506 */
            for (long i14797 = 0; i14797 < 5120; ++i14797) {
                r506[i14797] = r504[i14797] == 0 ? r493[i14797] : (r490[i14797]);
            }
            memcpy(r488, r491, sizeof(int32_t) * 1);
            memcpy(r489, r505, sizeof(int32_t) * 5120);
            memcpy(r490, r506, sizeof(int32_t) * 5120);
        }
        memcpy(r507, r488, sizeof(int32_t) * 1);
        memcpy(r508, r489, sizeof(int32_t) * 5120);
        memcpy(r509, r490, sizeof(int32_t) * 5120);
        /* abs [abs] -> r510 */
        for (long i14798 = 0; i14798 < 81920; ++i14798) {
            r510[i14798] = abs32(r482[i14798]);
        }
        /* reduce_max [reduce_max] -> r511 */
        for (long i14799 = 0; i14799 < 5120; ++i14799) {
            r511[i14799] = (-2147483647 - 1);
        }
        for (long i14800 = 0; i14800 < 81920; ++i14800) {
            long t14802 = i14800;
            long c148010 = t14802 / 16384; t14802 %= 16384;
            long c148011 = t14802 / 16384; t14802 %= 16384;
            long c148012 = t14802 / 16; t14802 %= 16;
            long c148013 = t14802;
            r511[c148010 * 1024 + c148011 * 1024 + c148012 * 1] = max32(r511[c148010 * 1024 + c148011 * 1024 + c148012 * 1], r510[i14800]);
        }
        /* sub [sub] -> r512 */
        for (long i14803 = 0; i14803 < 5120; ++i14803) {
            r512[i14803] = sub32(r511[i14803], r55[0]);
        }
        /* loop [scan] -> r534 */
        memcpy(r513, r482, sizeof(int32_t) * 81920);
        memcpy(r514, r55, sizeof(int32_t) * 1);
        memcpy(r515, r13, sizeof(int32_t) * 1);
        memcpy(r516, r512, sizeof(int32_t) * 5120);
        memcpy(r517, r511, sizeof(int32_t) * 5120);
        for (long t14804 = 0; t14804 < 12; ++t14804) {
            /* add [add] -> r518 */
            for (long i15805 = 0; i15805 < 1; ++i15805) {
                r518[i15805] = add32(r515[0], r9[0]);
            }
            /* add [add] -> r519 */
            for (long i15806 = 0; i15806 < 5120; ++i15806) {
                r519[i15806] = add32(r516[i15806], r517[i15806]);
            }
            /* shra [shift_right_arithmetic] -> r520 */
            for (long i15807 = 0; i15807 < 5120; ++i15807) {
                r520[i15807] = asr32(r519[i15807], 1);
            }
            /* broadcast [broadcast_in_dim] -> r521 */
            for (long i15808 = 0; i15808 < 5120; ++i15808) {
                long t15810 = i15808;
                long c158090 = t15810 / 1024; t15810 %= 1024;
                long c158091 = t15810 / 1024; t15810 %= 1024;
                long c158092 = t15810 / 1; t15810 %= 1;
                long c158093 = t15810;
                r521[i15808] = r520[c158090 * 1024 + c158092 * 1];
            }
            /* sub [sub] -> r522 */
            for (long i15811 = 0; i15811 < 81920; ++i15811) {
                long t15813 = i15811;
                long c158120 = t15813 / 16384; t15813 %= 16384;
                long c158121 = t15813 / 16384; t15813 %= 16384;
                long c158122 = t15813 / 16; t15813 %= 16;
                long c158123 = t15813;
                r522[i15811] = sub32(r513[c158120 * 16384 + c158122 * 16 + c158123 * 1], r521[c158120 * 1024 + c158122 * 1]);
            }
            /* max [max] -> r523 */
            for (long i15814 = 0; i15814 < 81920; ++i15814) {
                r523[i15814] = max32(r522[i15814], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r524 */
            for (long i15815 = 0; i15815 < 5120; ++i15815) {
                r524[i15815] = 0;
            }
            for (long i15816 = 0; i15816 < 81920; ++i15816) {
                long t15818 = i15816;
                long c158170 = t15818 / 16384; t15818 %= 16384;
                long c158171 = t15818 / 16384; t15818 %= 16384;
                long c158172 = t15818 / 16; t15818 %= 16;
                long c158173 = t15818;
                r524[c158170 * 1024 + c158171 * 1024 + c158172 * 1] = add32(r524[c158170 * 1024 + c158171 * 1024 + c158172 * 1], r523[i15816]);
            }
            /* neg [neg] -> r525 */
            for (long i15819 = 0; i15819 < 81920; ++i15819) {
                r525[i15819] = neg32(r513[i15819]);
            }
            /* broadcast [broadcast_in_dim] -> r526 */
            for (long i15820 = 0; i15820 < 5120; ++i15820) {
                long t15822 = i15820;
                long c158210 = t15822 / 1024; t15822 %= 1024;
                long c158211 = t15822 / 1024; t15822 %= 1024;
                long c158212 = t15822 / 1; t15822 %= 1;
                long c158213 = t15822;
                r526[i15820] = r520[c158210 * 1024 + c158212 * 1];
            }
            /* sub [sub] -> r527 */
            for (long i15823 = 0; i15823 < 81920; ++i15823) {
                long t15825 = i15823;
                long c158240 = t15825 / 16384; t15825 %= 16384;
                long c158241 = t15825 / 16384; t15825 %= 16384;
                long c158242 = t15825 / 16; t15825 %= 16;
                long c158243 = t15825;
                r527[i15823] = sub32(r525[c158240 * 16384 + c158242 * 16 + c158243 * 1], r526[c158240 * 1024 + c158242 * 1]);
            }
            /* max [max] -> r528 */
            for (long i15826 = 0; i15826 < 81920; ++i15826) {
                r528[i15826] = max32(r527[i15826], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r529 */
            for (long i15827 = 0; i15827 < 5120; ++i15827) {
                r529[i15827] = 0;
            }
            for (long i15828 = 0; i15828 < 81920; ++i15828) {
                long t15830 = i15828;
                long c158290 = t15830 / 16384; t15830 %= 16384;
                long c158291 = t15830 / 16384; t15830 %= 16384;
                long c158292 = t15830 / 16; t15830 %= 16;
                long c158293 = t15830;
                r529[c158290 * 1024 + c158291 * 1024 + c158292 * 1] = add32(r529[c158290 * 1024 + c158291 * 1024 + c158292 * 1], r528[i15828]);
            }
            /* add [add] -> r530 */
            for (long i15831 = 0; i15831 < 5120; ++i15831) {
                r530[i15831] = add32(r524[i15831], r529[i15831]);
            }
            /* gt [gt] -> r531 */
            for (long i15832 = 0; i15832 < 5120; ++i15832) {
                r531[i15832] = r530[i15832] > r514[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r532 */
            for (long i15833 = 0; i15833 < 5120; ++i15833) {
                r532[i15833] = r531[i15833] == 0 ? r516[i15833] : (r520[i15833]);
            }
            /* select_n [select_n] -> r533 */
            for (long i15834 = 0; i15834 < 5120; ++i15834) {
                r533[i15834] = r531[i15834] == 0 ? r520[i15834] : (r517[i15834]);
            }
            memcpy(r515, r518, sizeof(int32_t) * 1);
            memcpy(r516, r532, sizeof(int32_t) * 5120);
            memcpy(r517, r533, sizeof(int32_t) * 5120);
        }
        memcpy(r534, r515, sizeof(int32_t) * 1);
        memcpy(r535, r516, sizeof(int32_t) * 5120);
        memcpy(r536, r517, sizeof(int32_t) * 5120);
        /* sub [sub] -> r537 */
        for (long i15835 = 0; i15835 < 5120; ++i15835) {
            r537[i15835] = sub32(r509[i15835], r536[i15835]);
        }
        memcpy(r538 + t12724 * 5120, r537, sizeof(int32_t) * 5120);
    }
    /* transpose [transpose] -> r539 */
    for (long i15836 = 0; i15836 < 20480; ++i15836) {
        long t15838 = i15836;
        long c158370 = t15838 / 4096; t15838 %= 4096;
        long c158371 = t15838 / 4096; t15838 %= 4096;
        long c158372 = t15838 / 1024; t15838 %= 1024;
        long c158373 = t15838;
        r539[i15836] = r538[c158370 * 1024 + c158371 * 1024 + c158372 * 5120 + c158373 * 1];
    }
    /* reshape [reshape] -> r540 */
    memcpy(r540, r539, sizeof(int32_t) * 20480);
    /* slice [slice] -> r541 */
    for (long i15839 = 0; i15839 < 20000; ++i15839) {
        long t15841 = i15839;
        long c158400 = t15841 / 4000; t15841 %= 4000;
        long c158401 = t15841 / 4000; t15841 %= 4000;
        long c158402 = t15841;
        r541[i15839] = r540[(0 + c158400 * 1) * 4096 + (0 + c158401 * 1) * 4096 + (0 + c158402 * 1) * 1];
    }
    /* transpose [transpose] -> r542 */
    for (long i15842 = 0; i15842 < 20000; ++i15842) {
        long t15844 = i15842;
        long c158430 = t15844 / 20000; t15844 %= 20000;
        long c158431 = t15844 / 4000; t15844 %= 4000;
        long c158432 = t15844;
        r542[i15842] = r541[c158430 * 4000 + c158431 * 4000 + c158432 * 1];
    }
    /* max [max] -> r543 */
    for (long i15845 = 0; i15845 < 20000; ++i15845) {
        r543[i15845] = max32(r542[i15845], r13[0]);
    }
    /* reduce_sum [reduce_sum] -> r544 */
    for (long i15846 = 0; i15846 < 5; ++i15846) {
        r544[i15846] = 0;
    }
    for (long i15847 = 0; i15847 < 20000; ++i15847) {
        long t15849 = i15847;
        long c158480 = t15849 / 20000; t15849 %= 20000;
        long c158481 = t15849 / 4000; t15849 %= 4000;
        long c158482 = t15849;
        r544[c158480 * 5 + c158481 * 1] = add32(r544[c158480 * 5 + c158481 * 1], r543[i15847]);
    }
    /* shl [shift_left] -> r546 */
    for (long i15850 = 0; i15850 < 5; ++i15850) {
        r546[i15850] = shl32(r544[i15850], 2);
    }
    /* shl [shift_left] -> r547 */
    for (long i15851 = 0; i15851 < 4000; ++i15851) {
        r547[i15851] = shl32(r443[i15851], 1);
    }
    /* rev [rev] -> r548 */
    for (long i15852 = 0; i15852 < 6; ++i15852) {
        long t15854 = i15852;
        long c158530 = t15854 / 6; t15854 %= 6;
        long c158531 = t15854;
        r548[i15852] = r2[c158530 * 6 + (6 - 1 - c158531) * 1];
    }
    /* reshape [reshape] -> r549 */
    memcpy(r549, r548, sizeof(int32_t) * 6);
    /* convert [convert_element_type] -> r550 */
    for (long i15855 = 0; i15855 < 1; ++i15855) {
        r550[i15855] = (int32_t)r13[0];
    }
    /* pad [pad] -> r551 */
    for (long i15856 = 0; i15856 < 4005; ++i15856) {
        r551[i15856] = r550[0];
    }
    for (long i15857 = 0; i15857 < 4000; ++i15857) {
        long t15859 = i15857;
        long c158580 = t15859 / 4000; t15859 %= 4000;
        long c158581 = t15859;
        long d15860 = 0 + c158580 * 1;
        long d15861 = 5 + c158581 * 1;
        if (d15860 >= 0 && d15860 < 1 && d15861 >= 0 && d15861 < 4005) r551[d15860 * 4005 + d15861 * 1] = r547[i15857];
    }
    /* convert [convert_element_type] -> r552 */
    for (long i15862 = 0; i15862 < 1; ++i15862) {
        r552[i15862] = (int32_t)r13[0];
    }
    /* pad [pad] -> r553 */
    for (long i15863 = 0; i15863 < 4101; ++i15863) {
        r553[i15863] = r552[0];
    }
    for (long i15864 = 0; i15864 < 4005; ++i15864) {
        long t15866 = i15864;
        long c158650 = t15866 / 4005; t15866 %= 4005;
        long c158651 = t15866;
        long d15867 = 0 + c158650 * 1;
        long d15868 = 0 + c158651 * 1;
        if (d15867 >= 0 && d15867 < 1 && d15868 >= 0 && d15868 < 4101) r553[d15867 * 4101 + d15868 * 1] = r551[i15864];
    }
    /* iota [iota] -> r554 */
    for (long i15869 = 0; i15869 < 1024; ++i15869) {
        long t15871 = i15869;
        long c158700 = t15871;
        r554[i15869] = (int32_t)c158700;
    }
    /* broadcast [broadcast_in_dim] -> r555 */
    for (long i15872 = 0; i15872 < 1024; ++i15872) {
        long t15874 = i15872;
        long c158730 = t15874 / 1; t15874 %= 1;
        long c158731 = t15874;
        r555[i15872] = r554[c158730 * 1];
    }
    /* iota [iota] -> r556 */
    for (long i15875 = 0; i15875 < 6; ++i15875) {
        long t15877 = i15875;
        long c158760 = t15877;
        r556[i15875] = (int32_t)c158760;
    }
    /* broadcast [broadcast_in_dim] -> r557 */
    for (long i15878 = 0; i15878 < 6; ++i15878) {
        long t15880 = i15878;
        long c158790 = t15880 / 6; t15880 %= 6;
        long c158791 = t15880;
        r557[i15878] = r556[c158791 * 1];
    }
    /* add [add] -> r558 */
    for (long i15881 = 0; i15881 < 6144; ++i15881) {
        long t15883 = i15881;
        long c158820 = t15883 / 6; t15883 %= 6;
        long c158821 = t15883;
        r558[i15881] = add32(r555[c158820 * 1], r557[c158821 * 1]);
    }
    /* iota [iota] -> r559 */
    for (long i15884 = 0; i15884 < 4; ++i15884) {
        long t15886 = i15884;
        long c158850 = t15886;
        r559[i15884] = (int32_t)c158850;
    }
    /* shl [mul] -> r560 */
    for (long i15887 = 0; i15887 < 4; ++i15887) {
        r560[i15887] = shl32(r559[i15887], 10);
    }
    /* loop [scan] -> r641 */
    memcpy(r561, r553, sizeof(int32_t) * 4101);
    memcpy(r562, r558, sizeof(int32_t) * 6144);
    memcpy(r563, r549, sizeof(int32_t) * 6);
    for (long t15888 = 0; t15888 < 4; ++t15888) {
        memcpy(r564, r560 + t15888 * 1, sizeof(int32_t) * 1);
        /* lt [lt] -> r565 */
        for (long i16889 = 0; i16889 < 1; ++i16889) {
            r565[i16889] = r564[0] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r567 */
        for (long i16890 = 0; i16890 < 1; ++i16890) {
            r567[i16890] = add32(r564[0], r566[0]);
        }
        /* select_n [select_n] -> r568 */
        for (long i16891 = 0; i16891 < 1; ++i16891) {
            r568[i16891] = r565[0] == 0 ? r564[0] : (r567[0]);
        }
        /* dynamic_slice [dynamic_slice] -> r569 */
        long s16892 = clamp_start((long)r13[0], 1, 1);
        long s16893 = clamp_start((long)r568[0], 4101, 1029);
        {
        for (long i16894 = 0; i16894 < 1029; ++i16894) {
            long t16896 = i16894;
            long c168950 = t16896 / 1029; t16896 %= 1029;
            long c168951 = t16896;
            r569[i16894] = r561[(s16892 + c168950) * 4101 + (s16893 + c168951) * 1];
        }
        }
        /* lt [lt] -> r570 */
        for (long i16897 = 0; i16897 < 6144; ++i16897) {
            r570[i16897] = r562[i16897] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r571 */
        for (long i16898 = 0; i16898 < 6144; ++i16898) {
            r571[i16898] = add32(r562[i16898], r141[0]);
        }
        /* select_n [select_n] -> r572 */
        for (long i16899 = 0; i16899 < 6144; ++i16899) {
            r572[i16899] = r570[i16899] == 0 ? r562[i16899] : (r571[i16899]);
        }
        /* broadcast [broadcast_in_dim] -> r573 */
        for (long i16900 = 0; i16900 < 6144; ++i16900) {
            long t16902 = i16900;
            long c169010 = t16902 / 6; t16902 %= 6;
            long c169011 = t16902 / 1; t16902 %= 1;
            long c169012 = t16902;
            r573[i16900] = r572[c169010 * 6 + c169011 * 1];
        }
        /* gather [gather] -> r574 */
        for (long i16903 = 0; i16903 < 6144; ++i16903) {
            long t16905 = i16903;
            long c169040 = t16905 / 6144; t16905 %= 6144;
            long c169041 = t16905 / 6; t16905 %= 6;
            long c169042 = t16905;
            long row16906 = c169041 * 6 + c169042 * 1;
            long s16907 = clamp_start((long)r573[row16906 + 0], 1029, 1);
            r574[i16903] = r569[c169040 * 1029 + s16907 * 1];
        }
        /* broadcast [broadcast_in_dim] -> r575 */
        for (long i16908 = 0; i16908 < 6144; ++i16908) {
            long t16910 = i16908;
            long c169090 = t16910 / 6144; t16910 %= 6144;
            long c169091 = t16910 / 6144; t16910 %= 6144;
            long c169092 = t16910 / 6; t16910 %= 6;
            long c169093 = t16910;
            r575[i16908] = r574[c169092 * 6 + c169093 * 1];
        }
        /* add [add] -> r576 */
        for (long i16911 = 0; i16911 < 6144; ++i16911) {
            long t16913 = i16911;
            long c169120 = t16913 / 6144; t16913 %= 6144;
            long c169121 = t16913 / 6144; t16913 %= 6144;
            long c169122 = t16913 / 6; t16913 %= 6;
            long c169123 = t16913;
            r576[i16911] = add32(r563[c169123 * 1], r575[c169122 * 6 + c169123 * 1]);
        }
        /* convert [convert_element_type] -> r577 */
        for (long i16914 = 0; i16914 < 1; ++i16914) {
            r577[i16914] = (int32_t)r42[0];
        }
        /* max [max] -> r578 */
        for (long i16915 = 0; i16915 < 6144; ++i16915) {
            r578[i16915] = max32(r577[0], r576[i16915]);
        }
        /* convert [convert_element_type] -> r579 */
        for (long i16916 = 0; i16916 < 1; ++i16916) {
            r579[i16916] = (int32_t)r43[0];
        }
        /* min [min] -> r580 */
        for (long i16917 = 0; i16917 < 6144; ++i16917) {
            r580[i16917] = min32(r579[0], r578[i16917]);
        }
        /* sub [sub] -> r581 */
        for (long i16918 = 0; i16918 < 6144; ++i16918) {
            long t16920 = i16918;
            long c169190 = t16920 / 6144; t16920 %= 6144;
            long c169191 = t16920 / 6144; t16920 %= 6144;
            long c169192 = t16920 / 6; t16920 %= 6;
            long c169193 = t16920;
            r581[i16918] = sub32(r563[c169193 * 1], r575[c169192 * 6 + c169193 * 1]);
        }
        /* convert [convert_element_type] -> r582 */
        for (long i16921 = 0; i16921 < 1; ++i16921) {
            r582[i16921] = (int32_t)r42[0];
        }
        /* max [max] -> r583 */
        for (long i16922 = 0; i16922 < 6144; ++i16922) {
            r583[i16922] = max32(r582[0], r581[i16922]);
        }
        /* convert [convert_element_type] -> r584 */
        for (long i16923 = 0; i16923 < 1; ++i16923) {
            r584[i16923] = (int32_t)r43[0];
        }
        /* min [min] -> r585 */
        for (long i16924 = 0; i16924 < 6144; ++i16924) {
            r585[i16924] = min32(r584[0], r583[i16924]);
        }
        /* abs [abs] -> r586 */
        for (long i16925 = 0; i16925 < 6144; ++i16925) {
            r586[i16925] = abs32(r580[i16925]);
        }
        /* reduce_max [reduce_max] -> r587 */
        for (long i16926 = 0; i16926 < 1024; ++i16926) {
            r587[i16926] = (-2147483647 - 1);
        }
        for (long i16927 = 0; i16927 < 6144; ++i16927) {
            long t16929 = i16927;
            long c169280 = t16929 / 6144; t16929 %= 6144;
            long c169281 = t16929 / 6144; t16929 %= 6144;
            long c169282 = t16929 / 6; t16929 %= 6;
            long c169283 = t16929;
            r587[c169280 * 1024 + c169281 * 1024 + c169282 * 1] = max32(r587[c169280 * 1024 + c169281 * 1024 + c169282 * 1], r586[i16927]);
        }
        /* sub [sub] -> r588 */
        for (long i16930 = 0; i16930 < 1024; ++i16930) {
            r588[i16930] = sub32(r587[i16930], r55[0]);
        }
        /* loop [scan] -> r610 */
        memcpy(r589, r580, sizeof(int32_t) * 6144);
        memcpy(r590, r55, sizeof(int32_t) * 1);
        memcpy(r591, r13, sizeof(int32_t) * 1);
        memcpy(r592, r588, sizeof(int32_t) * 1024);
        memcpy(r593, r587, sizeof(int32_t) * 1024);
        for (long t16931 = 0; t16931 < 12; ++t16931) {
            /* add [add] -> r594 */
            for (long i17932 = 0; i17932 < 1; ++i17932) {
                r594[i17932] = add32(r591[0], r9[0]);
            }
            /* add [add] -> r595 */
            for (long i17933 = 0; i17933 < 1024; ++i17933) {
                r595[i17933] = add32(r592[i17933], r593[i17933]);
            }
            /* shra [shift_right_arithmetic] -> r596 */
            for (long i17934 = 0; i17934 < 1024; ++i17934) {
                r596[i17934] = asr32(r595[i17934], 1);
            }
            /* broadcast [broadcast_in_dim] -> r597 */
            for (long i17935 = 0; i17935 < 1024; ++i17935) {
                long t17937 = i17935;
                long c179360 = t17937 / 1024; t17937 %= 1024;
                long c179361 = t17937 / 1024; t17937 %= 1024;
                long c179362 = t17937 / 1; t17937 %= 1;
                long c179363 = t17937;
                r597[i17935] = r596[c179362 * 1];
            }
            /* sub [sub] -> r598 */
            for (long i17938 = 0; i17938 < 6144; ++i17938) {
                long t17940 = i17938;
                long c179390 = t17940 / 6144; t17940 %= 6144;
                long c179391 = t17940 / 6144; t17940 %= 6144;
                long c179392 = t17940 / 6; t17940 %= 6;
                long c179393 = t17940;
                r598[i17938] = sub32(r589[c179392 * 6 + c179393 * 1], r597[c179392 * 1]);
            }
            /* max [max] -> r599 */
            for (long i17941 = 0; i17941 < 6144; ++i17941) {
                r599[i17941] = max32(r598[i17941], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r600 */
            for (long i17942 = 0; i17942 < 1024; ++i17942) {
                r600[i17942] = 0;
            }
            for (long i17943 = 0; i17943 < 6144; ++i17943) {
                long t17945 = i17943;
                long c179440 = t17945 / 6144; t17945 %= 6144;
                long c179441 = t17945 / 6144; t17945 %= 6144;
                long c179442 = t17945 / 6; t17945 %= 6;
                long c179443 = t17945;
                r600[c179440 * 1024 + c179441 * 1024 + c179442 * 1] = add32(r600[c179440 * 1024 + c179441 * 1024 + c179442 * 1], r599[i17943]);
            }
            /* neg [neg] -> r601 */
            for (long i17946 = 0; i17946 < 6144; ++i17946) {
                r601[i17946] = neg32(r589[i17946]);
            }
            /* broadcast [broadcast_in_dim] -> r602 */
            for (long i17947 = 0; i17947 < 1024; ++i17947) {
                long t17949 = i17947;
                long c179480 = t17949 / 1024; t17949 %= 1024;
                long c179481 = t17949 / 1024; t17949 %= 1024;
                long c179482 = t17949 / 1; t17949 %= 1;
                long c179483 = t17949;
                r602[i17947] = r596[c179482 * 1];
            }
            /* sub [sub] -> r603 */
            for (long i17950 = 0; i17950 < 6144; ++i17950) {
                long t17952 = i17950;
                long c179510 = t17952 / 6144; t17952 %= 6144;
                long c179511 = t17952 / 6144; t17952 %= 6144;
                long c179512 = t17952 / 6; t17952 %= 6;
                long c179513 = t17952;
                r603[i17950] = sub32(r601[c179512 * 6 + c179513 * 1], r602[c179512 * 1]);
            }
            /* max [max] -> r604 */
            for (long i17953 = 0; i17953 < 6144; ++i17953) {
                r604[i17953] = max32(r603[i17953], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r605 */
            for (long i17954 = 0; i17954 < 1024; ++i17954) {
                r605[i17954] = 0;
            }
            for (long i17955 = 0; i17955 < 6144; ++i17955) {
                long t17957 = i17955;
                long c179560 = t17957 / 6144; t17957 %= 6144;
                long c179561 = t17957 / 6144; t17957 %= 6144;
                long c179562 = t17957 / 6; t17957 %= 6;
                long c179563 = t17957;
                r605[c179560 * 1024 + c179561 * 1024 + c179562 * 1] = add32(r605[c179560 * 1024 + c179561 * 1024 + c179562 * 1], r604[i17955]);
            }
            /* add [add] -> r606 */
            for (long i17958 = 0; i17958 < 1024; ++i17958) {
                r606[i17958] = add32(r600[i17958], r605[i17958]);
            }
            /* gt [gt] -> r607 */
            for (long i17959 = 0; i17959 < 1024; ++i17959) {
                r607[i17959] = r606[i17959] > r590[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r608 */
            for (long i17960 = 0; i17960 < 1024; ++i17960) {
                r608[i17960] = r607[i17960] == 0 ? r592[i17960] : (r596[i17960]);
            }
            /* select_n [select_n] -> r609 */
            for (long i17961 = 0; i17961 < 1024; ++i17961) {
                r609[i17961] = r607[i17961] == 0 ? r596[i17961] : (r593[i17961]);
            }
            memcpy(r591, r594, sizeof(int32_t) * 1);
            memcpy(r592, r608, sizeof(int32_t) * 1024);
            memcpy(r593, r609, sizeof(int32_t) * 1024);
        }
        memcpy(r610, r591, sizeof(int32_t) * 1);
        memcpy(r611, r592, sizeof(int32_t) * 1024);
        memcpy(r612, r593, sizeof(int32_t) * 1024);
        /* abs [abs] -> r613 */
        for (long i17962 = 0; i17962 < 6144; ++i17962) {
            r613[i17962] = abs32(r585[i17962]);
        }
        /* reduce_max [reduce_max] -> r614 */
        for (long i17963 = 0; i17963 < 1024; ++i17963) {
            r614[i17963] = (-2147483647 - 1);
        }
        for (long i17964 = 0; i17964 < 6144; ++i17964) {
            long t17966 = i17964;
            long c179650 = t17966 / 6144; t17966 %= 6144;
            long c179651 = t17966 / 6144; t17966 %= 6144;
            long c179652 = t17966 / 6; t17966 %= 6;
            long c179653 = t17966;
            r614[c179650 * 1024 + c179651 * 1024 + c179652 * 1] = max32(r614[c179650 * 1024 + c179651 * 1024 + c179652 * 1], r613[i17964]);
        }
        /* sub [sub] -> r615 */
        for (long i17967 = 0; i17967 < 1024; ++i17967) {
            r615[i17967] = sub32(r614[i17967], r55[0]);
        }
        /* loop [scan] -> r637 */
        memcpy(r616, r585, sizeof(int32_t) * 6144);
        memcpy(r617, r55, sizeof(int32_t) * 1);
        memcpy(r618, r13, sizeof(int32_t) * 1);
        memcpy(r619, r615, sizeof(int32_t) * 1024);
        memcpy(r620, r614, sizeof(int32_t) * 1024);
        for (long t17968 = 0; t17968 < 12; ++t17968) {
            /* add [add] -> r621 */
            for (long i18969 = 0; i18969 < 1; ++i18969) {
                r621[i18969] = add32(r618[0], r9[0]);
            }
            /* add [add] -> r622 */
            for (long i18970 = 0; i18970 < 1024; ++i18970) {
                r622[i18970] = add32(r619[i18970], r620[i18970]);
            }
            /* shra [shift_right_arithmetic] -> r623 */
            for (long i18971 = 0; i18971 < 1024; ++i18971) {
                r623[i18971] = asr32(r622[i18971], 1);
            }
            /* broadcast [broadcast_in_dim] -> r624 */
            for (long i18972 = 0; i18972 < 1024; ++i18972) {
                long t18974 = i18972;
                long c189730 = t18974 / 1024; t18974 %= 1024;
                long c189731 = t18974 / 1024; t18974 %= 1024;
                long c189732 = t18974 / 1; t18974 %= 1;
                long c189733 = t18974;
                r624[i18972] = r623[c189732 * 1];
            }
            /* sub [sub] -> r625 */
            for (long i18975 = 0; i18975 < 6144; ++i18975) {
                long t18977 = i18975;
                long c189760 = t18977 / 6144; t18977 %= 6144;
                long c189761 = t18977 / 6144; t18977 %= 6144;
                long c189762 = t18977 / 6; t18977 %= 6;
                long c189763 = t18977;
                r625[i18975] = sub32(r616[c189762 * 6 + c189763 * 1], r624[c189762 * 1]);
            }
            /* max [max] -> r626 */
            for (long i18978 = 0; i18978 < 6144; ++i18978) {
                r626[i18978] = max32(r625[i18978], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r627 */
            for (long i18979 = 0; i18979 < 1024; ++i18979) {
                r627[i18979] = 0;
            }
            for (long i18980 = 0; i18980 < 6144; ++i18980) {
                long t18982 = i18980;
                long c189810 = t18982 / 6144; t18982 %= 6144;
                long c189811 = t18982 / 6144; t18982 %= 6144;
                long c189812 = t18982 / 6; t18982 %= 6;
                long c189813 = t18982;
                r627[c189810 * 1024 + c189811 * 1024 + c189812 * 1] = add32(r627[c189810 * 1024 + c189811 * 1024 + c189812 * 1], r626[i18980]);
            }
            /* neg [neg] -> r628 */
            for (long i18983 = 0; i18983 < 6144; ++i18983) {
                r628[i18983] = neg32(r616[i18983]);
            }
            /* broadcast [broadcast_in_dim] -> r629 */
            for (long i18984 = 0; i18984 < 1024; ++i18984) {
                long t18986 = i18984;
                long c189850 = t18986 / 1024; t18986 %= 1024;
                long c189851 = t18986 / 1024; t18986 %= 1024;
                long c189852 = t18986 / 1; t18986 %= 1;
                long c189853 = t18986;
                r629[i18984] = r623[c189852 * 1];
            }
            /* sub [sub] -> r630 */
            for (long i18987 = 0; i18987 < 6144; ++i18987) {
                long t18989 = i18987;
                long c189880 = t18989 / 6144; t18989 %= 6144;
                long c189881 = t18989 / 6144; t18989 %= 6144;
                long c189882 = t18989 / 6; t18989 %= 6;
                long c189883 = t18989;
                r630[i18987] = sub32(r628[c189882 * 6 + c189883 * 1], r629[c189882 * 1]);
            }
            /* max [max] -> r631 */
            for (long i18990 = 0; i18990 < 6144; ++i18990) {
                r631[i18990] = max32(r630[i18990], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r632 */
            for (long i18991 = 0; i18991 < 1024; ++i18991) {
                r632[i18991] = 0;
            }
            for (long i18992 = 0; i18992 < 6144; ++i18992) {
                long t18994 = i18992;
                long c189930 = t18994 / 6144; t18994 %= 6144;
                long c189931 = t18994 / 6144; t18994 %= 6144;
                long c189932 = t18994 / 6; t18994 %= 6;
                long c189933 = t18994;
                r632[c189930 * 1024 + c189931 * 1024 + c189932 * 1] = add32(r632[c189930 * 1024 + c189931 * 1024 + c189932 * 1], r631[i18992]);
            }
            /* add [add] -> r633 */
            for (long i18995 = 0; i18995 < 1024; ++i18995) {
                r633[i18995] = add32(r627[i18995], r632[i18995]);
            }
            /* gt [gt] -> r634 */
            for (long i18996 = 0; i18996 < 1024; ++i18996) {
                r634[i18996] = r633[i18996] > r617[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r635 */
            for (long i18997 = 0; i18997 < 1024; ++i18997) {
                r635[i18997] = r634[i18997] == 0 ? r619[i18997] : (r623[i18997]);
            }
            /* select_n [select_n] -> r636 */
            for (long i18998 = 0; i18998 < 1024; ++i18998) {
                r636[i18998] = r634[i18998] == 0 ? r623[i18998] : (r620[i18998]);
            }
            memcpy(r618, r621, sizeof(int32_t) * 1);
            memcpy(r619, r635, sizeof(int32_t) * 1024);
            memcpy(r620, r636, sizeof(int32_t) * 1024);
        }
        memcpy(r637, r618, sizeof(int32_t) * 1);
        memcpy(r638, r619, sizeof(int32_t) * 1024);
        memcpy(r639, r620, sizeof(int32_t) * 1024);
        /* sub [sub] -> r640 */
        for (long i18999 = 0; i18999 < 1024; ++i18999) {
            r640[i18999] = sub32(r612[i18999], r639[i18999]);
        }
        memcpy(r641 + t15888 * 1024, r640, sizeof(int32_t) * 1024);
    }
    /* transpose [transpose] -> r642 */
    for (long i19000 = 0; i19000 < 4096; ++i19000) {
        long t19002 = i19000;
        long c190010 = t19002 / 4096; t19002 %= 4096;
        long c190011 = t19002 / 4096; t19002 %= 4096;
        long c190012 = t19002 / 1024; t19002 %= 1024;
        long c190013 = t19002;
        r642[i19000] = r641[c190010 * 1024 + c190011 * 1024 + c190012 * 1024 + c190013 * 1];
    }
    /* reshape [reshape] -> r643 */
    memcpy(r643, r642, sizeof(int32_t) * 4096);
    /* slice [slice] -> r644 */
    for (long i19003 = 0; i19003 < 4000; ++i19003) {
        long t19005 = i19003;
        long c190040 = t19005 / 4000; t19005 %= 4000;
        long c190041 = t19005 / 4000; t19005 %= 4000;
        long c190042 = t19005;
        r644[i19003] = r643[(0 + c190040 * 1) * 4096 + (0 + c190041 * 1) * 4096 + (0 + c190042 * 1) * 1];
    }
    /* transpose [transpose] -> r645 */
    for (long i19006 = 0; i19006 < 4000; ++i19006) {
        long t19008 = i19006;
        long c190070 = t19008 / 4000; t19008 %= 4000;
        long c190071 = t19008 / 4000; t19008 %= 4000;
        long c190072 = t19008;
        r645[i19006] = r644[c190070 * 4000 + c190071 * 4000 + c190072 * 1];
    }
    /* slice [slice] -> r646 */
    for (long i19009 = 0; i19009 < 4000; ++i19009) {
        long t19011 = i19009;
        long c190100 = t19011 / 4000; t19011 %= 4000;
        long c190101 = t19011 / 4000; t19011 %= 4000;
        long c190102 = t19011;
        r646[i19009] = r645[(0 + c190100 * 1) * 4000 + (0 + c190101 * 1) * 4000 + (0 + c190102 * 1) * 1];
    }
    /* reshape [squeeze] -> r647 */
    memcpy(r647, r646, sizeof(int32_t) * 4000);
    /* shra [shift_right_arithmetic] -> r648 */
    for (long i19012 = 0; i19012 < 4000; ++i19012) {
        r648[i19012] = asr32(r647[i19012], 1);
    }
    /* convert [convert_element_type] -> r649 */
    for (long i19013 = 0; i19013 < 1; ++i19013) {
        r649[i19013] = (int32_t)r220[0];
    }
    /* max [max] -> r650 */
    for (long i19014 = 0; i19014 < 4000; ++i19014) {
        r650[i19014] = max32(r649[0], r648[i19014]);
    }
    /* convert [convert_element_type] -> r651 */
    for (long i19015 = 0; i19015 < 1; ++i19015) {
        r651[i19015] = (int32_t)r221[0];
    }
    /* min [min] -> r652 */
    for (long i19016 = 0; i19016 < 4000; ++i19016) {
        r652[i19016] = min32(r651[0], r650[i19016]);
    }
    /* iota [iota] -> r653 */
    for (long i19017 = 0; i19017 < 2000; ++i19017) {
        long t19019 = i19017;
        long c190180 = t19019;
        r653[i19017] = (int32_t)c190180;
    }
    /* shl [mul] -> r654 */
    for (long i19020 = 0; i19020 < 2000; ++i19020) {
        r654[i19020] = shl32(r653[i19020], 1);
    }
    /* add [add] -> r655 */
    for (long i19021 = 0; i19021 < 2000; ++i19021) {
        r655[i19021] = add32(r13[0], r654[i19021]);
    }
    /* broadcast [broadcast_in_dim] -> r656 */
    for (long i19022 = 0; i19022 < 2000; ++i19022) {
        long t19024 = i19022;
        long c190230 = t19024 / 1; t19024 %= 1;
        long c190231 = t19024;
        r656[i19022] = r655[c190230 * 1];
    }
    /* gather [gather] -> r657 */
    for (long i19025 = 0; i19025 < 2000; ++i19025) {
        long t19027 = i19025;
        long c190260 = t19027 / 2000; t19027 %= 2000;
        long c190261 = t19027;
        long row19028 = c190261 * 1;
        long s19029 = clamp_start((long)r656[row19028 + 0], 4000, 1);
        r657[i19025] = r652[c190260 * 4000 + s19029 * 1];
    }
    /* shl [shift_left] -> r658 */
    for (long i19030 = 0; i19030 < 2000; ++i19030) {
        r658[i19030] = shl32(r657[i19030], 1);
    }
    /* rev [rev] -> r659 */
    for (long i19031 = 0; i19031 < 80; ++i19031) {
        long t19033 = i19031;
        long c190320 = t19033 / 16; t19033 %= 16;
        long c190321 = t19033;
        r659[i19031] = r1[c190320 * 16 + (16 - 1 - c190321) * 1];
    }
    /* reshape [reshape] -> r660 */
    memcpy(r660, r659, sizeof(int32_t) * 80);
    /* convert [convert_element_type] -> r661 */
    for (long i19034 = 0; i19034 < 1; ++i19034) {
        r661[i19034] = (int32_t)r13[0];
    }
    /* pad [pad] -> r662 */
    for (long i19035 = 0; i19035 < 2015; ++i19035) {
        r662[i19035] = r661[0];
    }
    for (long i19036 = 0; i19036 < 2000; ++i19036) {
        long t19038 = i19036;
        long c190370 = t19038 / 2000; t19038 %= 2000;
        long c190371 = t19038;
        long d19039 = 0 + c190370 * 1;
        long d19040 = 15 + c190371 * 1;
        if (d19039 >= 0 && d19039 < 1 && d19040 >= 0 && d19040 < 2015) r662[d19039 * 2015 + d19040 * 1] = r658[i19036];
    }
    /* convert [convert_element_type] -> r663 */
    for (long i19041 = 0; i19041 < 1; ++i19041) {
        r663[i19041] = (int32_t)r13[0];
    }
    /* pad [pad] -> r664 */
    for (long i19042 = 0; i19042 < 2063; ++i19042) {
        r664[i19042] = r663[0];
    }
    for (long i19043 = 0; i19043 < 2015; ++i19043) {
        long t19045 = i19043;
        long c190440 = t19045 / 2015; t19045 %= 2015;
        long c190441 = t19045;
        long d19046 = 0 + c190440 * 1;
        long d19047 = 0 + c190441 * 1;
        if (d19046 >= 0 && d19046 < 1 && d19047 >= 0 && d19047 < 2063) r664[d19046 * 2063 + d19047 * 1] = r662[i19043];
    }
    /* iota [iota] -> r665 */
    for (long i19048 = 0; i19048 < 1024; ++i19048) {
        long t19050 = i19048;
        long c190490 = t19050;
        r665[i19048] = (int32_t)c190490;
    }
    /* broadcast [broadcast_in_dim] -> r666 */
    for (long i19051 = 0; i19051 < 1024; ++i19051) {
        long t19053 = i19051;
        long c190520 = t19053 / 1; t19053 %= 1;
        long c190521 = t19053;
        r666[i19051] = r665[c190520 * 1];
    }
    /* iota [iota] -> r667 */
    for (long i19054 = 0; i19054 < 16; ++i19054) {
        long t19056 = i19054;
        long c190550 = t19056;
        r667[i19054] = (int32_t)c190550;
    }
    /* broadcast [broadcast_in_dim] -> r668 */
    for (long i19057 = 0; i19057 < 16; ++i19057) {
        long t19059 = i19057;
        long c190580 = t19059 / 16; t19059 %= 16;
        long c190581 = t19059;
        r668[i19057] = r667[c190581 * 1];
    }
    /* add [add] -> r669 */
    for (long i19060 = 0; i19060 < 16384; ++i19060) {
        long t19062 = i19060;
        long c190610 = t19062 / 16; t19062 %= 16;
        long c190611 = t19062;
        r669[i19060] = add32(r666[c190610 * 1], r668[c190611 * 1]);
    }
    /* iota [iota] -> r670 */
    for (long i19063 = 0; i19063 < 2; ++i19063) {
        long t19065 = i19063;
        long c190640 = t19065;
        r670[i19063] = (int32_t)c190640;
    }
    /* shl [mul] -> r671 */
    for (long i19066 = 0; i19066 < 2; ++i19066) {
        r671[i19066] = shl32(r670[i19066], 10);
    }
    /* loop [scan] -> r752 */
    memcpy(r672, r664, sizeof(int32_t) * 2063);
    memcpy(r673, r669, sizeof(int32_t) * 16384);
    memcpy(r674, r660, sizeof(int32_t) * 80);
    for (long t19067 = 0; t19067 < 2; ++t19067) {
        memcpy(r675, r671 + t19067 * 1, sizeof(int32_t) * 1);
        /* lt [lt] -> r676 */
        for (long i20068 = 0; i20068 < 1; ++i20068) {
            r676[i20068] = r675[0] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r678 */
        for (long i20069 = 0; i20069 < 1; ++i20069) {
            r678[i20069] = add32(r675[0], r677[0]);
        }
        /* select_n [select_n] -> r679 */
        for (long i20070 = 0; i20070 < 1; ++i20070) {
            r679[i20070] = r676[0] == 0 ? r675[0] : (r678[0]);
        }
        /* dynamic_slice [dynamic_slice] -> r680 */
        long s20071 = clamp_start((long)r13[0], 1, 1);
        long s20072 = clamp_start((long)r679[0], 2063, 1039);
        {
        for (long i20073 = 0; i20073 < 1039; ++i20073) {
            long t20075 = i20073;
            long c200740 = t20075 / 1039; t20075 %= 1039;
            long c200741 = t20075;
            r680[i20073] = r672[(s20071 + c200740) * 2063 + (s20072 + c200741) * 1];
        }
        }
        /* lt [lt] -> r681 */
        for (long i20076 = 0; i20076 < 16384; ++i20076) {
            r681[i20076] = r673[i20076] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r682 */
        for (long i20077 = 0; i20077 < 16384; ++i20077) {
            r682[i20077] = add32(r673[i20077], r35[0]);
        }
        /* select_n [select_n] -> r683 */
        for (long i20078 = 0; i20078 < 16384; ++i20078) {
            r683[i20078] = r681[i20078] == 0 ? r673[i20078] : (r682[i20078]);
        }
        /* broadcast [broadcast_in_dim] -> r684 */
        for (long i20079 = 0; i20079 < 16384; ++i20079) {
            long t20081 = i20079;
            long c200800 = t20081 / 16; t20081 %= 16;
            long c200801 = t20081 / 1; t20081 %= 1;
            long c200802 = t20081;
            r684[i20079] = r683[c200800 * 16 + c200801 * 1];
        }
        /* gather [gather] -> r685 */
        for (long i20082 = 0; i20082 < 16384; ++i20082) {
            long t20084 = i20082;
            long c200830 = t20084 / 16384; t20084 %= 16384;
            long c200831 = t20084 / 16; t20084 %= 16;
            long c200832 = t20084;
            long row20085 = c200831 * 16 + c200832 * 1;
            long s20086 = clamp_start((long)r684[row20085 + 0], 1039, 1);
            r685[i20082] = r680[c200830 * 1039 + s20086 * 1];
        }
        /* broadcast [broadcast_in_dim] -> r686 */
        for (long i20087 = 0; i20087 < 16384; ++i20087) {
            long t20089 = i20087;
            long c200880 = t20089 / 16384; t20089 %= 16384;
            long c200881 = t20089 / 16384; t20089 %= 16384;
            long c200882 = t20089 / 16; t20089 %= 16;
            long c200883 = t20089;
            r686[i20087] = r685[c200882 * 16 + c200883 * 1];
        }
        /* add [add] -> r687 */
        for (long i20090 = 0; i20090 < 81920; ++i20090) {
            long t20092 = i20090;
            long c200910 = t20092 / 16384; t20092 %= 16384;
            long c200911 = t20092 / 16384; t20092 %= 16384;
            long c200912 = t20092 / 16; t20092 %= 16;
            long c200913 = t20092;
            r687[i20090] = add32(r674[c200910 * 16 + c200913 * 1], r686[c200912 * 16 + c200913 * 1]);
        }
        /* convert [convert_element_type] -> r688 */
        for (long i20093 = 0; i20093 < 1; ++i20093) {
            r688[i20093] = (int32_t)r42[0];
        }
        /* max [max] -> r689 */
        for (long i20094 = 0; i20094 < 81920; ++i20094) {
            r689[i20094] = max32(r688[0], r687[i20094]);
        }
        /* convert [convert_element_type] -> r690 */
        for (long i20095 = 0; i20095 < 1; ++i20095) {
            r690[i20095] = (int32_t)r43[0];
        }
        /* min [min] -> r691 */
        for (long i20096 = 0; i20096 < 81920; ++i20096) {
            r691[i20096] = min32(r690[0], r689[i20096]);
        }
        /* sub [sub] -> r692 */
        for (long i20097 = 0; i20097 < 81920; ++i20097) {
            long t20099 = i20097;
            long c200980 = t20099 / 16384; t20099 %= 16384;
            long c200981 = t20099 / 16384; t20099 %= 16384;
            long c200982 = t20099 / 16; t20099 %= 16;
            long c200983 = t20099;
            r692[i20097] = sub32(r674[c200980 * 16 + c200983 * 1], r686[c200982 * 16 + c200983 * 1]);
        }
        /* convert [convert_element_type] -> r693 */
        for (long i20100 = 0; i20100 < 1; ++i20100) {
            r693[i20100] = (int32_t)r42[0];
        }
        /* max [max] -> r694 */
        for (long i20101 = 0; i20101 < 81920; ++i20101) {
            r694[i20101] = max32(r693[0], r692[i20101]);
        }
        /* convert [convert_element_type] -> r695 */
        for (long i20102 = 0; i20102 < 1; ++i20102) {
            r695[i20102] = (int32_t)r43[0];
        }
        /* min [min] -> r696 */
        for (long i20103 = 0; i20103 < 81920; ++i20103) {
            r696[i20103] = min32(r695[0], r694[i20103]);
        }
        /* abs [abs] -> r697 */
        for (long i20104 = 0; i20104 < 81920; ++i20104) {
            r697[i20104] = abs32(r691[i20104]);
        }
        /* reduce_max [reduce_max] -> r698 */
        for (long i20105 = 0; i20105 < 5120; ++i20105) {
            r698[i20105] = (-2147483647 - 1);
        }
        for (long i20106 = 0; i20106 < 81920; ++i20106) {
            long t20108 = i20106;
            long c201070 = t20108 / 16384; t20108 %= 16384;
            long c201071 = t20108 / 16384; t20108 %= 16384;
            long c201072 = t20108 / 16; t20108 %= 16;
            long c201073 = t20108;
            r698[c201070 * 1024 + c201071 * 1024 + c201072 * 1] = max32(r698[c201070 * 1024 + c201071 * 1024 + c201072 * 1], r697[i20106]);
        }
        /* sub [sub] -> r699 */
        for (long i20109 = 0; i20109 < 5120; ++i20109) {
            r699[i20109] = sub32(r698[i20109], r55[0]);
        }
        /* loop [scan] -> r721 */
        memcpy(r700, r691, sizeof(int32_t) * 81920);
        memcpy(r701, r55, sizeof(int32_t) * 1);
        memcpy(r702, r13, sizeof(int32_t) * 1);
        memcpy(r703, r699, sizeof(int32_t) * 5120);
        memcpy(r704, r698, sizeof(int32_t) * 5120);
        for (long t20110 = 0; t20110 < 12; ++t20110) {
            /* add [add] -> r705 */
            for (long i21111 = 0; i21111 < 1; ++i21111) {
                r705[i21111] = add32(r702[0], r9[0]);
            }
            /* add [add] -> r706 */
            for (long i21112 = 0; i21112 < 5120; ++i21112) {
                r706[i21112] = add32(r703[i21112], r704[i21112]);
            }
            /* shra [shift_right_arithmetic] -> r707 */
            for (long i21113 = 0; i21113 < 5120; ++i21113) {
                r707[i21113] = asr32(r706[i21113], 1);
            }
            /* broadcast [broadcast_in_dim] -> r708 */
            for (long i21114 = 0; i21114 < 5120; ++i21114) {
                long t21116 = i21114;
                long c211150 = t21116 / 1024; t21116 %= 1024;
                long c211151 = t21116 / 1024; t21116 %= 1024;
                long c211152 = t21116 / 1; t21116 %= 1;
                long c211153 = t21116;
                r708[i21114] = r707[c211150 * 1024 + c211152 * 1];
            }
            /* sub [sub] -> r709 */
            for (long i21117 = 0; i21117 < 81920; ++i21117) {
                long t21119 = i21117;
                long c211180 = t21119 / 16384; t21119 %= 16384;
                long c211181 = t21119 / 16384; t21119 %= 16384;
                long c211182 = t21119 / 16; t21119 %= 16;
                long c211183 = t21119;
                r709[i21117] = sub32(r700[c211180 * 16384 + c211182 * 16 + c211183 * 1], r708[c211180 * 1024 + c211182 * 1]);
            }
            /* max [max] -> r710 */
            for (long i21120 = 0; i21120 < 81920; ++i21120) {
                r710[i21120] = max32(r709[i21120], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r711 */
            for (long i21121 = 0; i21121 < 5120; ++i21121) {
                r711[i21121] = 0;
            }
            for (long i21122 = 0; i21122 < 81920; ++i21122) {
                long t21124 = i21122;
                long c211230 = t21124 / 16384; t21124 %= 16384;
                long c211231 = t21124 / 16384; t21124 %= 16384;
                long c211232 = t21124 / 16; t21124 %= 16;
                long c211233 = t21124;
                r711[c211230 * 1024 + c211231 * 1024 + c211232 * 1] = add32(r711[c211230 * 1024 + c211231 * 1024 + c211232 * 1], r710[i21122]);
            }
            /* neg [neg] -> r712 */
            for (long i21125 = 0; i21125 < 81920; ++i21125) {
                r712[i21125] = neg32(r700[i21125]);
            }
            /* broadcast [broadcast_in_dim] -> r713 */
            for (long i21126 = 0; i21126 < 5120; ++i21126) {
                long t21128 = i21126;
                long c211270 = t21128 / 1024; t21128 %= 1024;
                long c211271 = t21128 / 1024; t21128 %= 1024;
                long c211272 = t21128 / 1; t21128 %= 1;
                long c211273 = t21128;
                r713[i21126] = r707[c211270 * 1024 + c211272 * 1];
            }
            /* sub [sub] -> r714 */
            for (long i21129 = 0; i21129 < 81920; ++i21129) {
                long t21131 = i21129;
                long c211300 = t21131 / 16384; t21131 %= 16384;
                long c211301 = t21131 / 16384; t21131 %= 16384;
                long c211302 = t21131 / 16; t21131 %= 16;
                long c211303 = t21131;
                r714[i21129] = sub32(r712[c211300 * 16384 + c211302 * 16 + c211303 * 1], r713[c211300 * 1024 + c211302 * 1]);
            }
            /* max [max] -> r715 */
            for (long i21132 = 0; i21132 < 81920; ++i21132) {
                r715[i21132] = max32(r714[i21132], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r716 */
            for (long i21133 = 0; i21133 < 5120; ++i21133) {
                r716[i21133] = 0;
            }
            for (long i21134 = 0; i21134 < 81920; ++i21134) {
                long t21136 = i21134;
                long c211350 = t21136 / 16384; t21136 %= 16384;
                long c211351 = t21136 / 16384; t21136 %= 16384;
                long c211352 = t21136 / 16; t21136 %= 16;
                long c211353 = t21136;
                r716[c211350 * 1024 + c211351 * 1024 + c211352 * 1] = add32(r716[c211350 * 1024 + c211351 * 1024 + c211352 * 1], r715[i21134]);
            }
            /* add [add] -> r717 */
            for (long i21137 = 0; i21137 < 5120; ++i21137) {
                r717[i21137] = add32(r711[i21137], r716[i21137]);
            }
            /* gt [gt] -> r718 */
            for (long i21138 = 0; i21138 < 5120; ++i21138) {
                r718[i21138] = r717[i21138] > r701[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r719 */
            for (long i21139 = 0; i21139 < 5120; ++i21139) {
                r719[i21139] = r718[i21139] == 0 ? r703[i21139] : (r707[i21139]);
            }
            /* select_n [select_n] -> r720 */
            for (long i21140 = 0; i21140 < 5120; ++i21140) {
                r720[i21140] = r718[i21140] == 0 ? r707[i21140] : (r704[i21140]);
            }
            memcpy(r702, r705, sizeof(int32_t) * 1);
            memcpy(r703, r719, sizeof(int32_t) * 5120);
            memcpy(r704, r720, sizeof(int32_t) * 5120);
        }
        memcpy(r721, r702, sizeof(int32_t) * 1);
        memcpy(r722, r703, sizeof(int32_t) * 5120);
        memcpy(r723, r704, sizeof(int32_t) * 5120);
        /* abs [abs] -> r724 */
        for (long i21141 = 0; i21141 < 81920; ++i21141) {
            r724[i21141] = abs32(r696[i21141]);
        }
        /* reduce_max [reduce_max] -> r725 */
        for (long i21142 = 0; i21142 < 5120; ++i21142) {
            r725[i21142] = (-2147483647 - 1);
        }
        for (long i21143 = 0; i21143 < 81920; ++i21143) {
            long t21145 = i21143;
            long c211440 = t21145 / 16384; t21145 %= 16384;
            long c211441 = t21145 / 16384; t21145 %= 16384;
            long c211442 = t21145 / 16; t21145 %= 16;
            long c211443 = t21145;
            r725[c211440 * 1024 + c211441 * 1024 + c211442 * 1] = max32(r725[c211440 * 1024 + c211441 * 1024 + c211442 * 1], r724[i21143]);
        }
        /* sub [sub] -> r726 */
        for (long i21146 = 0; i21146 < 5120; ++i21146) {
            r726[i21146] = sub32(r725[i21146], r55[0]);
        }
        /* loop [scan] -> r748 */
        memcpy(r727, r696, sizeof(int32_t) * 81920);
        memcpy(r728, r55, sizeof(int32_t) * 1);
        memcpy(r729, r13, sizeof(int32_t) * 1);
        memcpy(r730, r726, sizeof(int32_t) * 5120);
        memcpy(r731, r725, sizeof(int32_t) * 5120);
        for (long t21147 = 0; t21147 < 12; ++t21147) {
            /* add [add] -> r732 */
            for (long i22148 = 0; i22148 < 1; ++i22148) {
                r732[i22148] = add32(r729[0], r9[0]);
            }
            /* add [add] -> r733 */
            for (long i22149 = 0; i22149 < 5120; ++i22149) {
                r733[i22149] = add32(r730[i22149], r731[i22149]);
            }
            /* shra [shift_right_arithmetic] -> r734 */
            for (long i22150 = 0; i22150 < 5120; ++i22150) {
                r734[i22150] = asr32(r733[i22150], 1);
            }
            /* broadcast [broadcast_in_dim] -> r735 */
            for (long i22151 = 0; i22151 < 5120; ++i22151) {
                long t22153 = i22151;
                long c221520 = t22153 / 1024; t22153 %= 1024;
                long c221521 = t22153 / 1024; t22153 %= 1024;
                long c221522 = t22153 / 1; t22153 %= 1;
                long c221523 = t22153;
                r735[i22151] = r734[c221520 * 1024 + c221522 * 1];
            }
            /* sub [sub] -> r736 */
            for (long i22154 = 0; i22154 < 81920; ++i22154) {
                long t22156 = i22154;
                long c221550 = t22156 / 16384; t22156 %= 16384;
                long c221551 = t22156 / 16384; t22156 %= 16384;
                long c221552 = t22156 / 16; t22156 %= 16;
                long c221553 = t22156;
                r736[i22154] = sub32(r727[c221550 * 16384 + c221552 * 16 + c221553 * 1], r735[c221550 * 1024 + c221552 * 1]);
            }
            /* max [max] -> r737 */
            for (long i22157 = 0; i22157 < 81920; ++i22157) {
                r737[i22157] = max32(r736[i22157], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r738 */
            for (long i22158 = 0; i22158 < 5120; ++i22158) {
                r738[i22158] = 0;
            }
            for (long i22159 = 0; i22159 < 81920; ++i22159) {
                long t22161 = i22159;
                long c221600 = t22161 / 16384; t22161 %= 16384;
                long c221601 = t22161 / 16384; t22161 %= 16384;
                long c221602 = t22161 / 16; t22161 %= 16;
                long c221603 = t22161;
                r738[c221600 * 1024 + c221601 * 1024 + c221602 * 1] = add32(r738[c221600 * 1024 + c221601 * 1024 + c221602 * 1], r737[i22159]);
            }
            /* neg [neg] -> r739 */
            for (long i22162 = 0; i22162 < 81920; ++i22162) {
                r739[i22162] = neg32(r727[i22162]);
            }
            /* broadcast [broadcast_in_dim] -> r740 */
            for (long i22163 = 0; i22163 < 5120; ++i22163) {
                long t22165 = i22163;
                long c221640 = t22165 / 1024; t22165 %= 1024;
                long c221641 = t22165 / 1024; t22165 %= 1024;
                long c221642 = t22165 / 1; t22165 %= 1;
                long c221643 = t22165;
                r740[i22163] = r734[c221640 * 1024 + c221642 * 1];
            }
            /* sub [sub] -> r741 */
            for (long i22166 = 0; i22166 < 81920; ++i22166) {
                long t22168 = i22166;
                long c221670 = t22168 / 16384; t22168 %= 16384;
                long c221671 = t22168 / 16384; t22168 %= 16384;
                long c221672 = t22168 / 16; t22168 %= 16;
                long c221673 = t22168;
                r741[i22166] = sub32(r739[c221670 * 16384 + c221672 * 16 + c221673 * 1], r740[c221670 * 1024 + c221672 * 1]);
            }
            /* max [max] -> r742 */
            for (long i22169 = 0; i22169 < 81920; ++i22169) {
                r742[i22169] = max32(r741[i22169], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r743 */
            for (long i22170 = 0; i22170 < 5120; ++i22170) {
                r743[i22170] = 0;
            }
            for (long i22171 = 0; i22171 < 81920; ++i22171) {
                long t22173 = i22171;
                long c221720 = t22173 / 16384; t22173 %= 16384;
                long c221721 = t22173 / 16384; t22173 %= 16384;
                long c221722 = t22173 / 16; t22173 %= 16;
                long c221723 = t22173;
                r743[c221720 * 1024 + c221721 * 1024 + c221722 * 1] = add32(r743[c221720 * 1024 + c221721 * 1024 + c221722 * 1], r742[i22171]);
            }
            /* add [add] -> r744 */
            for (long i22174 = 0; i22174 < 5120; ++i22174) {
                r744[i22174] = add32(r738[i22174], r743[i22174]);
            }
            /* gt [gt] -> r745 */
            for (long i22175 = 0; i22175 < 5120; ++i22175) {
                r745[i22175] = r744[i22175] > r728[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r746 */
            for (long i22176 = 0; i22176 < 5120; ++i22176) {
                r746[i22176] = r745[i22176] == 0 ? r730[i22176] : (r734[i22176]);
            }
            /* select_n [select_n] -> r747 */
            for (long i22177 = 0; i22177 < 5120; ++i22177) {
                r747[i22177] = r745[i22177] == 0 ? r734[i22177] : (r731[i22177]);
            }
            memcpy(r729, r732, sizeof(int32_t) * 1);
            memcpy(r730, r746, sizeof(int32_t) * 5120);
            memcpy(r731, r747, sizeof(int32_t) * 5120);
        }
        memcpy(r748, r729, sizeof(int32_t) * 1);
        memcpy(r749, r730, sizeof(int32_t) * 5120);
        memcpy(r750, r731, sizeof(int32_t) * 5120);
        /* sub [sub] -> r751 */
        for (long i22178 = 0; i22178 < 5120; ++i22178) {
            r751[i22178] = sub32(r723[i22178], r750[i22178]);
        }
        memcpy(r752 + t19067 * 5120, r751, sizeof(int32_t) * 5120);
    }
    /* transpose [transpose] -> r753 */
    for (long i22179 = 0; i22179 < 10240; ++i22179) {
        long t22181 = i22179;
        long c221800 = t22181 / 2048; t22181 %= 2048;
        long c221801 = t22181 / 2048; t22181 %= 2048;
        long c221802 = t22181 / 1024; t22181 %= 1024;
        long c221803 = t22181;
        r753[i22179] = r752[c221800 * 1024 + c221801 * 1024 + c221802 * 5120 + c221803 * 1];
    }
    /* reshape [reshape] -> r754 */
    memcpy(r754, r753, sizeof(int32_t) * 10240);
    /* slice [slice] -> r755 */
    for (long i22182 = 0; i22182 < 10000; ++i22182) {
        long t22184 = i22182;
        long c221830 = t22184 / 2000; t22184 %= 2000;
        long c221831 = t22184 / 2000; t22184 %= 2000;
        long c221832 = t22184;
        r755[i22182] = r754[(0 + c221830 * 1) * 2048 + (0 + c221831 * 1) * 2048 + (0 + c221832 * 1) * 1];
    }
    /* transpose [transpose] -> r756 */
    for (long i22185 = 0; i22185 < 10000; ++i22185) {
        long t22187 = i22185;
        long c221860 = t22187 / 10000; t22187 %= 10000;
        long c221861 = t22187 / 2000; t22187 %= 2000;
        long c221862 = t22187;
        r756[i22185] = r755[c221860 * 2000 + c221861 * 2000 + c221862 * 1];
    }
    /* max [max] -> r757 */
    for (long i22188 = 0; i22188 < 10000; ++i22188) {
        r757[i22188] = max32(r756[i22188], r13[0]);
    }
    /* reduce_sum [reduce_sum] -> r758 */
    for (long i22189 = 0; i22189 < 5; ++i22189) {
        r758[i22189] = 0;
    }
    for (long i22190 = 0; i22190 < 10000; ++i22190) {
        long t22192 = i22190;
        long c221910 = t22192 / 10000; t22192 %= 10000;
        long c221911 = t22192 / 2000; t22192 %= 2000;
        long c221912 = t22192;
        r758[c221910 * 5 + c221911 * 1] = add32(r758[c221910 * 5 + c221911 * 1], r757[i22190]);
    }
    /* shl [shift_left] -> r760 */
    for (long i22193 = 0; i22193 < 5; ++i22193) {
        r760[i22193] = shl32(r758[i22193], 3);
    }
    /* shl [shift_left] -> r761 */
    for (long i22194 = 0; i22194 < 2000; ++i22194) {
        r761[i22194] = shl32(r657[i22194], 1);
    }
    /* rev [rev] -> r762 */
    for (long i22195 = 0; i22195 < 6; ++i22195) {
        long t22197 = i22195;
        long c221960 = t22197 / 6; t22197 %= 6;
        long c221961 = t22197;
        r762[i22195] = r2[c221960 * 6 + (6 - 1 - c221961) * 1];
    }
    /* reshape [reshape] -> r763 */
    memcpy(r763, r762, sizeof(int32_t) * 6);
    /* convert [convert_element_type] -> r764 */
    for (long i22198 = 0; i22198 < 1; ++i22198) {
        r764[i22198] = (int32_t)r13[0];
    }
    /* pad [pad] -> r765 */
    for (long i22199 = 0; i22199 < 2005; ++i22199) {
        r765[i22199] = r764[0];
    }
    for (long i22200 = 0; i22200 < 2000; ++i22200) {
        long t22202 = i22200;
        long c222010 = t22202 / 2000; t22202 %= 2000;
        long c222011 = t22202;
        long d22203 = 0 + c222010 * 1;
        long d22204 = 5 + c222011 * 1;
        if (d22203 >= 0 && d22203 < 1 && d22204 >= 0 && d22204 < 2005) r765[d22203 * 2005 + d22204 * 1] = r761[i22200];
    }
    /* convert [convert_element_type] -> r766 */
    for (long i22205 = 0; i22205 < 1; ++i22205) {
        r766[i22205] = (int32_t)r13[0];
    }
    /* pad [pad] -> r767 */
    for (long i22206 = 0; i22206 < 2053; ++i22206) {
        r767[i22206] = r766[0];
    }
    for (long i22207 = 0; i22207 < 2005; ++i22207) {
        long t22209 = i22207;
        long c222080 = t22209 / 2005; t22209 %= 2005;
        long c222081 = t22209;
        long d22210 = 0 + c222080 * 1;
        long d22211 = 0 + c222081 * 1;
        if (d22210 >= 0 && d22210 < 1 && d22211 >= 0 && d22211 < 2053) r767[d22210 * 2053 + d22211 * 1] = r765[i22207];
    }
    /* iota [iota] -> r768 */
    for (long i22212 = 0; i22212 < 1024; ++i22212) {
        long t22214 = i22212;
        long c222130 = t22214;
        r768[i22212] = (int32_t)c222130;
    }
    /* broadcast [broadcast_in_dim] -> r769 */
    for (long i22215 = 0; i22215 < 1024; ++i22215) {
        long t22217 = i22215;
        long c222160 = t22217 / 1; t22217 %= 1;
        long c222161 = t22217;
        r769[i22215] = r768[c222160 * 1];
    }
    /* iota [iota] -> r770 */
    for (long i22218 = 0; i22218 < 6; ++i22218) {
        long t22220 = i22218;
        long c222190 = t22220;
        r770[i22218] = (int32_t)c222190;
    }
    /* broadcast [broadcast_in_dim] -> r771 */
    for (long i22221 = 0; i22221 < 6; ++i22221) {
        long t22223 = i22221;
        long c222220 = t22223 / 6; t22223 %= 6;
        long c222221 = t22223;
        r771[i22221] = r770[c222221 * 1];
    }
    /* add [add] -> r772 */
    for (long i22224 = 0; i22224 < 6144; ++i22224) {
        long t22226 = i22224;
        long c222250 = t22226 / 6; t22226 %= 6;
        long c222251 = t22226;
        r772[i22224] = add32(r769[c222250 * 1], r771[c222251 * 1]);
    }
    /* iota [iota] -> r773 */
    for (long i22227 = 0; i22227 < 2; ++i22227) {
        long t22229 = i22227;
        long c222280 = t22229;
        r773[i22227] = (int32_t)c222280;
    }
    /* shl [mul] -> r774 */
    for (long i22230 = 0; i22230 < 2; ++i22230) {
        r774[i22230] = shl32(r773[i22230], 10);
    }
    /* loop [scan] -> r855 */
    memcpy(r775, r767, sizeof(int32_t) * 2053);
    memcpy(r776, r772, sizeof(int32_t) * 6144);
    memcpy(r777, r763, sizeof(int32_t) * 6);
    for (long t22231 = 0; t22231 < 2; ++t22231) {
        memcpy(r778, r774 + t22231 * 1, sizeof(int32_t) * 1);
        /* lt [lt] -> r779 */
        for (long i23232 = 0; i23232 < 1; ++i23232) {
            r779[i23232] = r778[0] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r781 */
        for (long i23233 = 0; i23233 < 1; ++i23233) {
            r781[i23233] = add32(r778[0], r780[0]);
        }
        /* select_n [select_n] -> r782 */
        for (long i23234 = 0; i23234 < 1; ++i23234) {
            r782[i23234] = r779[0] == 0 ? r778[0] : (r781[0]);
        }
        /* dynamic_slice [dynamic_slice] -> r783 */
        long s23235 = clamp_start((long)r13[0], 1, 1);
        long s23236 = clamp_start((long)r782[0], 2053, 1029);
        {
        for (long i23237 = 0; i23237 < 1029; ++i23237) {
            long t23239 = i23237;
            long c232380 = t23239 / 1029; t23239 %= 1029;
            long c232381 = t23239;
            r783[i23237] = r775[(s23235 + c232380) * 2053 + (s23236 + c232381) * 1];
        }
        }
        /* lt [lt] -> r784 */
        for (long i23240 = 0; i23240 < 6144; ++i23240) {
            r784[i23240] = r776[i23240] < r13[0] ? 1 : 0;
        }
        /* add [add] -> r785 */
        for (long i23241 = 0; i23241 < 6144; ++i23241) {
            r785[i23241] = add32(r776[i23241], r141[0]);
        }
        /* select_n [select_n] -> r786 */
        for (long i23242 = 0; i23242 < 6144; ++i23242) {
            r786[i23242] = r784[i23242] == 0 ? r776[i23242] : (r785[i23242]);
        }
        /* broadcast [broadcast_in_dim] -> r787 */
        for (long i23243 = 0; i23243 < 6144; ++i23243) {
            long t23245 = i23243;
            long c232440 = t23245 / 6; t23245 %= 6;
            long c232441 = t23245 / 1; t23245 %= 1;
            long c232442 = t23245;
            r787[i23243] = r786[c232440 * 6 + c232441 * 1];
        }
        /* gather [gather] -> r788 */
        for (long i23246 = 0; i23246 < 6144; ++i23246) {
            long t23248 = i23246;
            long c232470 = t23248 / 6144; t23248 %= 6144;
            long c232471 = t23248 / 6; t23248 %= 6;
            long c232472 = t23248;
            long row23249 = c232471 * 6 + c232472 * 1;
            long s23250 = clamp_start((long)r787[row23249 + 0], 1029, 1);
            r788[i23246] = r783[c232470 * 1029 + s23250 * 1];
        }
        /* broadcast [broadcast_in_dim] -> r789 */
        for (long i23251 = 0; i23251 < 6144; ++i23251) {
            long t23253 = i23251;
            long c232520 = t23253 / 6144; t23253 %= 6144;
            long c232521 = t23253 / 6144; t23253 %= 6144;
            long c232522 = t23253 / 6; t23253 %= 6;
            long c232523 = t23253;
            r789[i23251] = r788[c232522 * 6 + c232523 * 1];
        }
        /* add [add] -> r790 */
        for (long i23254 = 0; i23254 < 6144; ++i23254) {
            long t23256 = i23254;
            long c232550 = t23256 / 6144; t23256 %= 6144;
            long c232551 = t23256 / 6144; t23256 %= 6144;
            long c232552 = t23256 / 6; t23256 %= 6;
            long c232553 = t23256;
            r790[i23254] = add32(r777[c232553 * 1], r789[c232552 * 6 + c232553 * 1]);
        }
        /* convert [convert_element_type] -> r791 */
        for (long i23257 = 0; i23257 < 1; ++i23257) {
            r791[i23257] = (int32_t)r42[0];
        }
        /* max [max] -> r792 */
        for (long i23258 = 0; i23258 < 6144; ++i23258) {
            r792[i23258] = max32(r791[0], r790[i23258]);
        }
        /* convert [convert_element_type] -> r793 */
        for (long i23259 = 0; i23259 < 1; ++i23259) {
            r793[i23259] = (int32_t)r43[0];
        }
        /* min [min] -> r794 */
        for (long i23260 = 0; i23260 < 6144; ++i23260) {
            r794[i23260] = min32(r793[0], r792[i23260]);
        }
        /* sub [sub] -> r795 */
        for (long i23261 = 0; i23261 < 6144; ++i23261) {
            long t23263 = i23261;
            long c232620 = t23263 / 6144; t23263 %= 6144;
            long c232621 = t23263 / 6144; t23263 %= 6144;
            long c232622 = t23263 / 6; t23263 %= 6;
            long c232623 = t23263;
            r795[i23261] = sub32(r777[c232623 * 1], r789[c232622 * 6 + c232623 * 1]);
        }
        /* convert [convert_element_type] -> r796 */
        for (long i23264 = 0; i23264 < 1; ++i23264) {
            r796[i23264] = (int32_t)r42[0];
        }
        /* max [max] -> r797 */
        for (long i23265 = 0; i23265 < 6144; ++i23265) {
            r797[i23265] = max32(r796[0], r795[i23265]);
        }
        /* convert [convert_element_type] -> r798 */
        for (long i23266 = 0; i23266 < 1; ++i23266) {
            r798[i23266] = (int32_t)r43[0];
        }
        /* min [min] -> r799 */
        for (long i23267 = 0; i23267 < 6144; ++i23267) {
            r799[i23267] = min32(r798[0], r797[i23267]);
        }
        /* abs [abs] -> r800 */
        for (long i23268 = 0; i23268 < 6144; ++i23268) {
            r800[i23268] = abs32(r794[i23268]);
        }
        /* reduce_max [reduce_max] -> r801 */
        for (long i23269 = 0; i23269 < 1024; ++i23269) {
            r801[i23269] = (-2147483647 - 1);
        }
        for (long i23270 = 0; i23270 < 6144; ++i23270) {
            long t23272 = i23270;
            long c232710 = t23272 / 6144; t23272 %= 6144;
            long c232711 = t23272 / 6144; t23272 %= 6144;
            long c232712 = t23272 / 6; t23272 %= 6;
            long c232713 = t23272;
            r801[c232710 * 1024 + c232711 * 1024 + c232712 * 1] = max32(r801[c232710 * 1024 + c232711 * 1024 + c232712 * 1], r800[i23270]);
        }
        /* sub [sub] -> r802 */
        for (long i23273 = 0; i23273 < 1024; ++i23273) {
            r802[i23273] = sub32(r801[i23273], r55[0]);
        }
        /* loop [scan] -> r824 */
        memcpy(r803, r794, sizeof(int32_t) * 6144);
        memcpy(r804, r55, sizeof(int32_t) * 1);
        memcpy(r805, r13, sizeof(int32_t) * 1);
        memcpy(r806, r802, sizeof(int32_t) * 1024);
        memcpy(r807, r801, sizeof(int32_t) * 1024);
        for (long t23274 = 0; t23274 < 12; ++t23274) {
            /* add [add] -> r808 */
            for (long i24275 = 0; i24275 < 1; ++i24275) {
                r808[i24275] = add32(r805[0], r9[0]);
            }
            /* add [add] -> r809 */
            for (long i24276 = 0; i24276 < 1024; ++i24276) {
                r809[i24276] = add32(r806[i24276], r807[i24276]);
            }
            /* shra [shift_right_arithmetic] -> r810 */
            for (long i24277 = 0; i24277 < 1024; ++i24277) {
                r810[i24277] = asr32(r809[i24277], 1);
            }
            /* broadcast [broadcast_in_dim] -> r811 */
            for (long i24278 = 0; i24278 < 1024; ++i24278) {
                long t24280 = i24278;
                long c242790 = t24280 / 1024; t24280 %= 1024;
                long c242791 = t24280 / 1024; t24280 %= 1024;
                long c242792 = t24280 / 1; t24280 %= 1;
                long c242793 = t24280;
                r811[i24278] = r810[c242792 * 1];
            }
            /* sub [sub] -> r812 */
            for (long i24281 = 0; i24281 < 6144; ++i24281) {
                long t24283 = i24281;
                long c242820 = t24283 / 6144; t24283 %= 6144;
                long c242821 = t24283 / 6144; t24283 %= 6144;
                long c242822 = t24283 / 6; t24283 %= 6;
                long c242823 = t24283;
                r812[i24281] = sub32(r803[c242822 * 6 + c242823 * 1], r811[c242822 * 1]);
            }
            /* max [max] -> r813 */
            for (long i24284 = 0; i24284 < 6144; ++i24284) {
                r813[i24284] = max32(r812[i24284], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r814 */
            for (long i24285 = 0; i24285 < 1024; ++i24285) {
                r814[i24285] = 0;
            }
            for (long i24286 = 0; i24286 < 6144; ++i24286) {
                long t24288 = i24286;
                long c242870 = t24288 / 6144; t24288 %= 6144;
                long c242871 = t24288 / 6144; t24288 %= 6144;
                long c242872 = t24288 / 6; t24288 %= 6;
                long c242873 = t24288;
                r814[c242870 * 1024 + c242871 * 1024 + c242872 * 1] = add32(r814[c242870 * 1024 + c242871 * 1024 + c242872 * 1], r813[i24286]);
            }
            /* neg [neg] -> r815 */
            for (long i24289 = 0; i24289 < 6144; ++i24289) {
                r815[i24289] = neg32(r803[i24289]);
            }
            /* broadcast [broadcast_in_dim] -> r816 */
            for (long i24290 = 0; i24290 < 1024; ++i24290) {
                long t24292 = i24290;
                long c242910 = t24292 / 1024; t24292 %= 1024;
                long c242911 = t24292 / 1024; t24292 %= 1024;
                long c242912 = t24292 / 1; t24292 %= 1;
                long c242913 = t24292;
                r816[i24290] = r810[c242912 * 1];
            }
            /* sub [sub] -> r817 */
            for (long i24293 = 0; i24293 < 6144; ++i24293) {
                long t24295 = i24293;
                long c242940 = t24295 / 6144; t24295 %= 6144;
                long c242941 = t24295 / 6144; t24295 %= 6144;
                long c242942 = t24295 / 6; t24295 %= 6;
                long c242943 = t24295;
                r817[i24293] = sub32(r815[c242942 * 6 + c242943 * 1], r816[c242942 * 1]);
            }
            /* max [max] -> r818 */
            for (long i24296 = 0; i24296 < 6144; ++i24296) {
                r818[i24296] = max32(r817[i24296], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r819 */
            for (long i24297 = 0; i24297 < 1024; ++i24297) {
                r819[i24297] = 0;
            }
            for (long i24298 = 0; i24298 < 6144; ++i24298) {
                long t24300 = i24298;
                long c242990 = t24300 / 6144; t24300 %= 6144;
                long c242991 = t24300 / 6144; t24300 %= 6144;
                long c242992 = t24300 / 6; t24300 %= 6;
                long c242993 = t24300;
                r819[c242990 * 1024 + c242991 * 1024 + c242992 * 1] = add32(r819[c242990 * 1024 + c242991 * 1024 + c242992 * 1], r818[i24298]);
            }
            /* add [add] -> r820 */
            for (long i24301 = 0; i24301 < 1024; ++i24301) {
                r820[i24301] = add32(r814[i24301], r819[i24301]);
            }
            /* gt [gt] -> r821 */
            for (long i24302 = 0; i24302 < 1024; ++i24302) {
                r821[i24302] = r820[i24302] > r804[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r822 */
            for (long i24303 = 0; i24303 < 1024; ++i24303) {
                r822[i24303] = r821[i24303] == 0 ? r806[i24303] : (r810[i24303]);
            }
            /* select_n [select_n] -> r823 */
            for (long i24304 = 0; i24304 < 1024; ++i24304) {
                r823[i24304] = r821[i24304] == 0 ? r810[i24304] : (r807[i24304]);
            }
            memcpy(r805, r808, sizeof(int32_t) * 1);
            memcpy(r806, r822, sizeof(int32_t) * 1024);
            memcpy(r807, r823, sizeof(int32_t) * 1024);
        }
        memcpy(r824, r805, sizeof(int32_t) * 1);
        memcpy(r825, r806, sizeof(int32_t) * 1024);
        memcpy(r826, r807, sizeof(int32_t) * 1024);
        /* abs [abs] -> r827 */
        for (long i24305 = 0; i24305 < 6144; ++i24305) {
            r827[i24305] = abs32(r799[i24305]);
        }
        /* reduce_max [reduce_max] -> r828 */
        for (long i24306 = 0; i24306 < 1024; ++i24306) {
            r828[i24306] = (-2147483647 - 1);
        }
        for (long i24307 = 0; i24307 < 6144; ++i24307) {
            long t24309 = i24307;
            long c243080 = t24309 / 6144; t24309 %= 6144;
            long c243081 = t24309 / 6144; t24309 %= 6144;
            long c243082 = t24309 / 6; t24309 %= 6;
            long c243083 = t24309;
            r828[c243080 * 1024 + c243081 * 1024 + c243082 * 1] = max32(r828[c243080 * 1024 + c243081 * 1024 + c243082 * 1], r827[i24307]);
        }
        /* sub [sub] -> r829 */
        for (long i24310 = 0; i24310 < 1024; ++i24310) {
            r829[i24310] = sub32(r828[i24310], r55[0]);
        }
        /* loop [scan] -> r851 */
        memcpy(r830, r799, sizeof(int32_t) * 6144);
        memcpy(r831, r55, sizeof(int32_t) * 1);
        memcpy(r832, r13, sizeof(int32_t) * 1);
        memcpy(r833, r829, sizeof(int32_t) * 1024);
        memcpy(r834, r828, sizeof(int32_t) * 1024);
        for (long t24311 = 0; t24311 < 12; ++t24311) {
            /* add [add] -> r835 */
            for (long i25312 = 0; i25312 < 1; ++i25312) {
                r835[i25312] = add32(r832[0], r9[0]);
            }
            /* add [add] -> r836 */
            for (long i25313 = 0; i25313 < 1024; ++i25313) {
                r836[i25313] = add32(r833[i25313], r834[i25313]);
            }
            /* shra [shift_right_arithmetic] -> r837 */
            for (long i25314 = 0; i25314 < 1024; ++i25314) {
                r837[i25314] = asr32(r836[i25314], 1);
            }
            /* broadcast [broadcast_in_dim] -> r838 */
            for (long i25315 = 0; i25315 < 1024; ++i25315) {
                long t25317 = i25315;
                long c253160 = t25317 / 1024; t25317 %= 1024;
                long c253161 = t25317 / 1024; t25317 %= 1024;
                long c253162 = t25317 / 1; t25317 %= 1;
                long c253163 = t25317;
                r838[i25315] = r837[c253162 * 1];
            }
            /* sub [sub] -> r839 */
            for (long i25318 = 0; i25318 < 6144; ++i25318) {
                long t25320 = i25318;
                long c253190 = t25320 / 6144; t25320 %= 6144;
                long c253191 = t25320 / 6144; t25320 %= 6144;
                long c253192 = t25320 / 6; t25320 %= 6;
                long c253193 = t25320;
                r839[i25318] = sub32(r830[c253192 * 6 + c253193 * 1], r838[c253192 * 1]);
            }
            /* max [max] -> r840 */
            for (long i25321 = 0; i25321 < 6144; ++i25321) {
                r840[i25321] = max32(r839[i25321], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r841 */
            for (long i25322 = 0; i25322 < 1024; ++i25322) {
                r841[i25322] = 0;
            }
            for (long i25323 = 0; i25323 < 6144; ++i25323) {
                long t25325 = i25323;
                long c253240 = t25325 / 6144; t25325 %= 6144;
                long c253241 = t25325 / 6144; t25325 %= 6144;
                long c253242 = t25325 / 6; t25325 %= 6;
                long c253243 = t25325;
                r841[c253240 * 1024 + c253241 * 1024 + c253242 * 1] = add32(r841[c253240 * 1024 + c253241 * 1024 + c253242 * 1], r840[i25323]);
            }
            /* neg [neg] -> r842 */
            for (long i25326 = 0; i25326 < 6144; ++i25326) {
                r842[i25326] = neg32(r830[i25326]);
            }
            /* broadcast [broadcast_in_dim] -> r843 */
            for (long i25327 = 0; i25327 < 1024; ++i25327) {
                long t25329 = i25327;
                long c253280 = t25329 / 1024; t25329 %= 1024;
                long c253281 = t25329 / 1024; t25329 %= 1024;
                long c253282 = t25329 / 1; t25329 %= 1;
                long c253283 = t25329;
                r843[i25327] = r837[c253282 * 1];
            }
            /* sub [sub] -> r844 */
            for (long i25330 = 0; i25330 < 6144; ++i25330) {
                long t25332 = i25330;
                long c253310 = t25332 / 6144; t25332 %= 6144;
                long c253311 = t25332 / 6144; t25332 %= 6144;
                long c253312 = t25332 / 6; t25332 %= 6;
                long c253313 = t25332;
                r844[i25330] = sub32(r842[c253312 * 6 + c253313 * 1], r843[c253312 * 1]);
            }
            /* max [max] -> r845 */
            for (long i25333 = 0; i25333 < 6144; ++i25333) {
                r845[i25333] = max32(r844[i25333], r13[0]);
            }
            /* reduce_sum [reduce_sum] -> r846 */
            for (long i25334 = 0; i25334 < 1024; ++i25334) {
                r846[i25334] = 0;
            }
            for (long i25335 = 0; i25335 < 6144; ++i25335) {
                long t25337 = i25335;
                long c253360 = t25337 / 6144; t25337 %= 6144;
                long c253361 = t25337 / 6144; t25337 %= 6144;
                long c253362 = t25337 / 6; t25337 %= 6;
                long c253363 = t25337;
                r846[c253360 * 1024 + c253361 * 1024 + c253362 * 1] = add32(r846[c253360 * 1024 + c253361 * 1024 + c253362 * 1], r845[i25335]);
            }
            /* add [add] -> r847 */
            for (long i25338 = 0; i25338 < 1024; ++i25338) {
                r847[i25338] = add32(r841[i25338], r846[i25338]);
            }
            /* gt [gt] -> r848 */
            for (long i25339 = 0; i25339 < 1024; ++i25339) {
                r848[i25339] = r847[i25339] > r831[0] ? 1 : 0;
            }
            /* select_n [select_n] -> r849 */
            for (long i25340 = 0; i25340 < 1024; ++i25340) {
                r849[i25340] = r848[i25340] == 0 ? r833[i25340] : (r837[i25340]);
            }
            /* select_n [select_n] -> r850 */
            for (long i25341 = 0; i25341 < 1024; ++i25341) {
                r850[i25341] = r848[i25341] == 0 ? r837[i25341] : (r834[i25341]);
            }
            memcpy(r832, r835, sizeof(int32_t) * 1);
            memcpy(r833, r849, sizeof(int32_t) * 1024);
            memcpy(r834, r850, sizeof(int32_t) * 1024);
        }
        memcpy(r851, r832, sizeof(int32_t) * 1);
        memcpy(r852, r833, sizeof(int32_t) * 1024);
        memcpy(r853, r834, sizeof(int32_t) * 1024);
        /* sub [sub] -> r854 */
        for (long i25342 = 0; i25342 < 1024; ++i25342) {
            r854[i25342] = sub32(r826[i25342], r853[i25342]);
        }
        memcpy(r855 + t22231 * 1024, r854, sizeof(int32_t) * 1024);
    }
    /* transpose [transpose] -> r856 */
    for (long i25343 = 0; i25343 < 2048; ++i25343) {
        long t25345 = i25343;
        long c253440 = t25345 / 2048; t25345 %= 2048;
        long c253441 = t25345 / 2048; t25345 %= 2048;
        long c253442 = t25345 / 1024; t25345 %= 1024;
        long c253443 = t25345;
        r856[i25343] = r855[c253440 * 1024 + c253441 * 1024 + c253442 * 1024 + c253443 * 1];
    }
    /* reshape [reshape] -> r857 */
    memcpy(r857, r856, sizeof(int32_t) * 2048);
    /* slice [slice] -> r858 */
    for (long i25346 = 0; i25346 < 2000; ++i25346) {
        long t25348 = i25346;
        long c253470 = t25348 / 2000; t25348 %= 2000;
        long c253471 = t25348 / 2000; t25348 %= 2000;
        long c253472 = t25348;
        r858[i25346] = r857[(0 + c253470 * 1) * 2048 + (0 + c253471 * 1) * 2048 + (0 + c253472 * 1) * 1];
    }
    /* transpose [transpose] -> r859 */
    for (long i25349 = 0; i25349 < 2000; ++i25349) {
        long t25351 = i25349;
        long c253500 = t25351 / 2000; t25351 %= 2000;
        long c253501 = t25351 / 2000; t25351 %= 2000;
        long c253502 = t25351;
        r859[i25349] = r858[c253500 * 2000 + c253501 * 2000 + c253502 * 1];
    }
    /* slice [slice] -> r860 */
    for (long i25352 = 0; i25352 < 2000; ++i25352) {
        long t25354 = i25352;
        long c253530 = t25354 / 2000; t25354 %= 2000;
        long c253531 = t25354 / 2000; t25354 %= 2000;
        long c253532 = t25354;
        r860[i25352] = r859[(0 + c253530 * 1) * 2000 + (0 + c253531 * 1) * 2000 + (0 + c253532 * 1) * 1];
    }
    /* reshape [squeeze] -> r861 */
    memcpy(r861, r860, sizeof(int32_t) * 2000);
    /* shra [shift_right_arithmetic] -> r862 */
    for (long i25355 = 0; i25355 < 2000; ++i25355) {
        r862[i25355] = asr32(r861[i25355], 1);
    }
    /* convert [convert_element_type] -> r863 */
    for (long i25356 = 0; i25356 < 1; ++i25356) {
        r863[i25356] = (int32_t)r220[0];
    }
    /* max [max] -> r864 */
    for (long i25357 = 0; i25357 < 2000; ++i25357) {
        r864[i25357] = max32(r863[0], r862[i25357]);
    }
    /* convert [convert_element_type] -> r865 */
    for (long i25358 = 0; i25358 < 1; ++i25358) {
        r865[i25358] = (int32_t)r221[0];
    }
    /* min [min] -> r866 */
    for (long i25359 = 0; i25359 < 2000; ++i25359) {
        r866[i25359] = min32(r865[0], r864[i25359]);
    }
    /* iota [iota] -> r867 */
    for (long i25360 = 0; i25360 < 1000; ++i25360) {
        long t25362 = i25360;
        long c253610 = t25362;
        r867[i25360] = (int32_t)c253610;
    }
    /* shl [mul] -> r868 */
    for (long i25363 = 0; i25363 < 1000; ++i25363) {
        r868[i25363] = shl32(r867[i25363], 1);
    }
    /* add [add] -> r869 */
    for (long i25364 = 0; i25364 < 1000; ++i25364) {
        r869[i25364] = add32(r13[0], r868[i25364]);
    }
    /* broadcast [broadcast_in_dim] -> r870 */
    for (long i25365 = 0; i25365 < 1000; ++i25365) {
        long t25367 = i25365;
        long c253660 = t25367 / 1; t25367 %= 1;
        long c253661 = t25367;
        r870[i25365] = r869[c253660 * 1];
    }
    /* gather [gather] -> r871 */
    for (long i25368 = 0; i25368 < 1000; ++i25368) {
        long t25370 = i25368;
        long c253690 = t25370 / 1000; t25370 %= 1000;
        long c253691 = t25370;
        long row25371 = c253691 * 1;
        long s25372 = clamp_start((long)r870[row25371 + 0], 2000, 1);
        r871[i25368] = r866[c253690 * 2000 + s25372 * 1];
    }
    /* shl [shift_left] -> r872 */
    for (long i25373 = 0; i25373 < 1000; ++i25373) {
        r872[i25373] = shl32(r871[i25373], 1);
    }
    /* rev [rev] -> r873 */
    for (long i25374 = 0; i25374 < 80; ++i25374) {
        long t25376 = i25374;
        long c253750 = t25376 / 16; t25376 %= 16;
        long c253751 = t25376;
        r873[i25374] = r1[c253750 * 16 + (16 - 1 - c253751) * 1];
    }
    /* reshape [reshape] -> r874 */
    memcpy(r874, r873, sizeof(int32_t) * 80);
    /* convert [convert_element_type] -> r875 */
    for (long i25377 = 0; i25377 < 1; ++i25377) {
        r875[i25377] = (int32_t)r13[0];
    }
    /* pad [pad] -> r876 */
    for (long i25378 = 0; i25378 < 1015; ++i25378) {
        r876[i25378] = r875[0];
    }
    for (long i25379 = 0; i25379 < 1000; ++i25379) {
        long t25381 = i25379;
        long c253800 = t25381 / 1000; t25381 %= 1000;
        long c253801 = t25381;
        long d25382 = 0 + c253800 * 1;
        long d25383 = 15 + c253801 * 1;
        if (d25382 >= 0 && d25382 < 1 && d25383 >= 0 && d25383 < 1015) r876[d25382 * 1015 + d25383 * 1] = r872[i25379];
    }
    /* iota [iota] -> r877 */
    for (long i25384 = 0; i25384 < 1000; ++i25384) {
        long t25386 = i25384;
        long c253850 = t25386;
        r877[i25384] = (int32_t)c253850;
    }
    /* broadcast [broadcast_in_dim] -> r878 */
    for (long i25387 = 0; i25387 < 1000; ++i25387) {
        long t25389 = i25387;
        long c253880 = t25389 / 1; t25389 %= 1;
        long c253881 = t25389;
        r878[i25387] = r877[c253880 * 1];
    }
    /* iota [iota] -> r879 */
    for (long i25390 = 0; i25390 < 16; ++i25390) {
        long t25392 = i25390;
        long c253910 = t25392;
        r879[i25390] = (int32_t)c253910;
    }
    /* broadcast [broadcast_in_dim] -> r880 */
    for (long i25393 = 0; i25393 < 16; ++i25393) {
        long t25395 = i25393;
        long c253940 = t25395 / 16; t25395 %= 16;
        long c253941 = t25395;
        r880[i25393] = r879[c253941 * 1];
    }
    /* add [add] -> r881 */
    for (long i25396 = 0; i25396 < 16000; ++i25396) {
        long t25398 = i25396;
        long c253970 = t25398 / 16; t25398 %= 16;
        long c253971 = t25398;
        r881[i25396] = add32(r878[c253970 * 1], r880[c253971 * 1]);
    }
    /* lt [lt] -> r882 */
    for (long i25399 = 0; i25399 < 16000; ++i25399) {
        r882[i25399] = r881[i25399] < r13[0] ? 1 : 0;
    }
    /* add [add] -> r884 */
    for (long i25400 = 0; i25400 < 16000; ++i25400) {
        r884[i25400] = add32(r881[i25400], r883[0]);
    }
    /* select_n [select_n] -> r885 */
    for (long i25401 = 0; i25401 < 16000; ++i25401) {
        r885[i25401] = r882[i25401] == 0 ? r881[i25401] : (r884[i25401]);
    }
    /* broadcast [broadcast_in_dim] -> r886 */
    for (long i25402 = 0; i25402 < 16000; ++i25402) {
        long t25404 = i25402;
        long c254030 = t25404 / 16; t25404 %= 16;
        long c254031 = t25404 / 1; t25404 %= 1;
        long c254032 = t25404;
        r886[i25402] = r885[c254030 * 16 + c254031 * 1];
    }
    /* gather [gather] -> r887 */
    for (long i25405 = 0; i25405 < 16000; ++i25405) {
        long t25407 = i25405;
        long c254060 = t25407 / 16000; t25407 %= 16000;
        long c254061 = t25407 / 16; t25407 %= 16;
        long c254062 = t25407;
        long row25408 = c254061 * 16 + c254062 * 1;
        long s25409 = clamp_start((long)r886[row25408 + 0], 1015, 1);
        r887[i25405] = r876[c254060 * 1015 + s25409 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r888 */
    for (long i25410 = 0; i25410 < 16000; ++i25410) {
        long t25412 = i25410;
        long c254110 = t25412 / 16000; t25412 %= 16000;
        long c254111 = t25412 / 16000; t25412 %= 16000;
        long c254112 = t25412 / 16; t25412 %= 16;
        long c254113 = t25412;
        r888[i25410] = r887[c254112 * 16 + c254113 * 1];
    }
    /* add [add] -> r889 */
    for (long i25413 = 0; i25413 < 80000; ++i25413) {
        long t25415 = i25413;
        long c254140 = t25415 / 16000; t25415 %= 16000;
        long c254141 = t25415 / 16000; t25415 %= 16000;
        long c254142 = t25415 / 16; t25415 %= 16;
        long c254143 = t25415;
        r889[i25413] = add32(r874[c254140 * 16 + c254143 * 1], r888[c254142 * 16 + c254143 * 1]);
    }
    /* convert [convert_element_type] -> r890 */
    for (long i25416 = 0; i25416 < 1; ++i25416) {
        r890[i25416] = (int32_t)r42[0];
    }
    /* max [max] -> r891 */
    for (long i25417 = 0; i25417 < 80000; ++i25417) {
        r891[i25417] = max32(r890[0], r889[i25417]);
    }
    /* convert [convert_element_type] -> r892 */
    for (long i25418 = 0; i25418 < 1; ++i25418) {
        r892[i25418] = (int32_t)r43[0];
    }
    /* min [min] -> r893 */
    for (long i25419 = 0; i25419 < 80000; ++i25419) {
        r893[i25419] = min32(r892[0], r891[i25419]);
    }
    /* sub [sub] -> r894 */
    for (long i25420 = 0; i25420 < 80000; ++i25420) {
        long t25422 = i25420;
        long c254210 = t25422 / 16000; t25422 %= 16000;
        long c254211 = t25422 / 16000; t25422 %= 16000;
        long c254212 = t25422 / 16; t25422 %= 16;
        long c254213 = t25422;
        r894[i25420] = sub32(r874[c254210 * 16 + c254213 * 1], r888[c254212 * 16 + c254213 * 1]);
    }
    /* convert [convert_element_type] -> r895 */
    for (long i25423 = 0; i25423 < 1; ++i25423) {
        r895[i25423] = (int32_t)r42[0];
    }
    /* max [max] -> r896 */
    for (long i25424 = 0; i25424 < 80000; ++i25424) {
        r896[i25424] = max32(r895[0], r894[i25424]);
    }
    /* convert [convert_element_type] -> r897 */
    for (long i25425 = 0; i25425 < 1; ++i25425) {
        r897[i25425] = (int32_t)r43[0];
    }
    /* min [min] -> r898 */
    for (long i25426 = 0; i25426 < 80000; ++i25426) {
        r898[i25426] = min32(r897[0], r896[i25426]);
    }
    /* abs [abs] -> r899 */
    for (long i25427 = 0; i25427 < 80000; ++i25427) {
        r899[i25427] = abs32(r893[i25427]);
    }
    /* reduce_max [reduce_max] -> r900 */
    for (long i25428 = 0; i25428 < 5000; ++i25428) {
        r900[i25428] = (-2147483647 - 1);
    }
    for (long i25429 = 0; i25429 < 80000; ++i25429) {
        long t25431 = i25429;
        long c254300 = t25431 / 16000; t25431 %= 16000;
        long c254301 = t25431 / 16000; t25431 %= 16000;
        long c254302 = t25431 / 16; t25431 %= 16;
        long c254303 = t25431;
        r900[c254300 * 1000 + c254301 * 1000 + c254302 * 1] = max32(r900[c254300 * 1000 + c254301 * 1000 + c254302 * 1], r899[i25429]);
    }
    /* sub [sub] -> r901 */
    for (long i25432 = 0; i25432 < 5000; ++i25432) {
        r901[i25432] = sub32(r900[i25432], r55[0]);
    }
    /* loop [scan] -> r923 */
    memcpy(r902, r893, sizeof(int32_t) * 80000);
    memcpy(r903, r55, sizeof(int32_t) * 1);
    memcpy(r904, r13, sizeof(int32_t) * 1);
    memcpy(r905, r901, sizeof(int32_t) * 5000);
    memcpy(r906, r900, sizeof(int32_t) * 5000);
    for (long t25433 = 0; t25433 < 12; ++t25433) {
        /* add [add] -> r907 */
        for (long i26434 = 0; i26434 < 1; ++i26434) {
            r907[i26434] = add32(r904[0], r9[0]);
        }
        /* add [add] -> r908 */
        for (long i26435 = 0; i26435 < 5000; ++i26435) {
            r908[i26435] = add32(r905[i26435], r906[i26435]);
        }
        /* shra [shift_right_arithmetic] -> r909 */
        for (long i26436 = 0; i26436 < 5000; ++i26436) {
            r909[i26436] = asr32(r908[i26436], 1);
        }
        /* broadcast [broadcast_in_dim] -> r910 */
        for (long i26437 = 0; i26437 < 5000; ++i26437) {
            long t26439 = i26437;
            long c264380 = t26439 / 1000; t26439 %= 1000;
            long c264381 = t26439 / 1000; t26439 %= 1000;
            long c264382 = t26439 / 1; t26439 %= 1;
            long c264383 = t26439;
            r910[i26437] = r909[c264380 * 1000 + c264382 * 1];
        }
        /* sub [sub] -> r911 */
        for (long i26440 = 0; i26440 < 80000; ++i26440) {
            long t26442 = i26440;
            long c264410 = t26442 / 16000; t26442 %= 16000;
            long c264411 = t26442 / 16000; t26442 %= 16000;
            long c264412 = t26442 / 16; t26442 %= 16;
            long c264413 = t26442;
            r911[i26440] = sub32(r902[c264410 * 16000 + c264412 * 16 + c264413 * 1], r910[c264410 * 1000 + c264412 * 1]);
        }
        /* max [max] -> r912 */
        for (long i26443 = 0; i26443 < 80000; ++i26443) {
            r912[i26443] = max32(r911[i26443], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r913 */
        for (long i26444 = 0; i26444 < 5000; ++i26444) {
            r913[i26444] = 0;
        }
        for (long i26445 = 0; i26445 < 80000; ++i26445) {
            long t26447 = i26445;
            long c264460 = t26447 / 16000; t26447 %= 16000;
            long c264461 = t26447 / 16000; t26447 %= 16000;
            long c264462 = t26447 / 16; t26447 %= 16;
            long c264463 = t26447;
            r913[c264460 * 1000 + c264461 * 1000 + c264462 * 1] = add32(r913[c264460 * 1000 + c264461 * 1000 + c264462 * 1], r912[i26445]);
        }
        /* neg [neg] -> r914 */
        for (long i26448 = 0; i26448 < 80000; ++i26448) {
            r914[i26448] = neg32(r902[i26448]);
        }
        /* broadcast [broadcast_in_dim] -> r915 */
        for (long i26449 = 0; i26449 < 5000; ++i26449) {
            long t26451 = i26449;
            long c264500 = t26451 / 1000; t26451 %= 1000;
            long c264501 = t26451 / 1000; t26451 %= 1000;
            long c264502 = t26451 / 1; t26451 %= 1;
            long c264503 = t26451;
            r915[i26449] = r909[c264500 * 1000 + c264502 * 1];
        }
        /* sub [sub] -> r916 */
        for (long i26452 = 0; i26452 < 80000; ++i26452) {
            long t26454 = i26452;
            long c264530 = t26454 / 16000; t26454 %= 16000;
            long c264531 = t26454 / 16000; t26454 %= 16000;
            long c264532 = t26454 / 16; t26454 %= 16;
            long c264533 = t26454;
            r916[i26452] = sub32(r914[c264530 * 16000 + c264532 * 16 + c264533 * 1], r915[c264530 * 1000 + c264532 * 1]);
        }
        /* max [max] -> r917 */
        for (long i26455 = 0; i26455 < 80000; ++i26455) {
            r917[i26455] = max32(r916[i26455], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r918 */
        for (long i26456 = 0; i26456 < 5000; ++i26456) {
            r918[i26456] = 0;
        }
        for (long i26457 = 0; i26457 < 80000; ++i26457) {
            long t26459 = i26457;
            long c264580 = t26459 / 16000; t26459 %= 16000;
            long c264581 = t26459 / 16000; t26459 %= 16000;
            long c264582 = t26459 / 16; t26459 %= 16;
            long c264583 = t26459;
            r918[c264580 * 1000 + c264581 * 1000 + c264582 * 1] = add32(r918[c264580 * 1000 + c264581 * 1000 + c264582 * 1], r917[i26457]);
        }
        /* add [add] -> r919 */
        for (long i26460 = 0; i26460 < 5000; ++i26460) {
            r919[i26460] = add32(r913[i26460], r918[i26460]);
        }
        /* gt [gt] -> r920 */
        for (long i26461 = 0; i26461 < 5000; ++i26461) {
            r920[i26461] = r919[i26461] > r903[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r921 */
        for (long i26462 = 0; i26462 < 5000; ++i26462) {
            r921[i26462] = r920[i26462] == 0 ? r905[i26462] : (r909[i26462]);
        }
        /* select_n [select_n] -> r922 */
        for (long i26463 = 0; i26463 < 5000; ++i26463) {
            r922[i26463] = r920[i26463] == 0 ? r909[i26463] : (r906[i26463]);
        }
        memcpy(r904, r907, sizeof(int32_t) * 1);
        memcpy(r905, r921, sizeof(int32_t) * 5000);
        memcpy(r906, r922, sizeof(int32_t) * 5000);
    }
    memcpy(r923, r904, sizeof(int32_t) * 1);
    memcpy(r924, r905, sizeof(int32_t) * 5000);
    memcpy(r925, r906, sizeof(int32_t) * 5000);
    /* abs [abs] -> r926 */
    for (long i26464 = 0; i26464 < 80000; ++i26464) {
        r926[i26464] = abs32(r898[i26464]);
    }
    /* reduce_max [reduce_max] -> r927 */
    for (long i26465 = 0; i26465 < 5000; ++i26465) {
        r927[i26465] = (-2147483647 - 1);
    }
    for (long i26466 = 0; i26466 < 80000; ++i26466) {
        long t26468 = i26466;
        long c264670 = t26468 / 16000; t26468 %= 16000;
        long c264671 = t26468 / 16000; t26468 %= 16000;
        long c264672 = t26468 / 16; t26468 %= 16;
        long c264673 = t26468;
        r927[c264670 * 1000 + c264671 * 1000 + c264672 * 1] = max32(r927[c264670 * 1000 + c264671 * 1000 + c264672 * 1], r926[i26466]);
    }
    /* sub [sub] -> r928 */
    for (long i26469 = 0; i26469 < 5000; ++i26469) {
        r928[i26469] = sub32(r927[i26469], r55[0]);
    }
    /* loop [scan] -> r950 */
    memcpy(r929, r898, sizeof(int32_t) * 80000);
    memcpy(r930, r55, sizeof(int32_t) * 1);
    memcpy(r931, r13, sizeof(int32_t) * 1);
    memcpy(r932, r928, sizeof(int32_t) * 5000);
    memcpy(r933, r927, sizeof(int32_t) * 5000);
    for (long t26470 = 0; t26470 < 12; ++t26470) {
        /* add [add] -> r934 */
        for (long i27471 = 0; i27471 < 1; ++i27471) {
            r934[i27471] = add32(r931[0], r9[0]);
        }
        /* add [add] -> r935 */
        for (long i27472 = 0; i27472 < 5000; ++i27472) {
            r935[i27472] = add32(r932[i27472], r933[i27472]);
        }
        /* shra [shift_right_arithmetic] -> r936 */
        for (long i27473 = 0; i27473 < 5000; ++i27473) {
            r936[i27473] = asr32(r935[i27473], 1);
        }
        /* broadcast [broadcast_in_dim] -> r937 */
        for (long i27474 = 0; i27474 < 5000; ++i27474) {
            long t27476 = i27474;
            long c274750 = t27476 / 1000; t27476 %= 1000;
            long c274751 = t27476 / 1000; t27476 %= 1000;
            long c274752 = t27476 / 1; t27476 %= 1;
            long c274753 = t27476;
            r937[i27474] = r936[c274750 * 1000 + c274752 * 1];
        }
        /* sub [sub] -> r938 */
        for (long i27477 = 0; i27477 < 80000; ++i27477) {
            long t27479 = i27477;
            long c274780 = t27479 / 16000; t27479 %= 16000;
            long c274781 = t27479 / 16000; t27479 %= 16000;
            long c274782 = t27479 / 16; t27479 %= 16;
            long c274783 = t27479;
            r938[i27477] = sub32(r929[c274780 * 16000 + c274782 * 16 + c274783 * 1], r937[c274780 * 1000 + c274782 * 1]);
        }
        /* max [max] -> r939 */
        for (long i27480 = 0; i27480 < 80000; ++i27480) {
            r939[i27480] = max32(r938[i27480], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r940 */
        for (long i27481 = 0; i27481 < 5000; ++i27481) {
            r940[i27481] = 0;
        }
        for (long i27482 = 0; i27482 < 80000; ++i27482) {
            long t27484 = i27482;
            long c274830 = t27484 / 16000; t27484 %= 16000;
            long c274831 = t27484 / 16000; t27484 %= 16000;
            long c274832 = t27484 / 16; t27484 %= 16;
            long c274833 = t27484;
            r940[c274830 * 1000 + c274831 * 1000 + c274832 * 1] = add32(r940[c274830 * 1000 + c274831 * 1000 + c274832 * 1], r939[i27482]);
        }
        /* neg [neg] -> r941 */
        for (long i27485 = 0; i27485 < 80000; ++i27485) {
            r941[i27485] = neg32(r929[i27485]);
        }
        /* broadcast [broadcast_in_dim] -> r942 */
        for (long i27486 = 0; i27486 < 5000; ++i27486) {
            long t27488 = i27486;
            long c274870 = t27488 / 1000; t27488 %= 1000;
            long c274871 = t27488 / 1000; t27488 %= 1000;
            long c274872 = t27488 / 1; t27488 %= 1;
            long c274873 = t27488;
            r942[i27486] = r936[c274870 * 1000 + c274872 * 1];
        }
        /* sub [sub] -> r943 */
        for (long i27489 = 0; i27489 < 80000; ++i27489) {
            long t27491 = i27489;
            long c274900 = t27491 / 16000; t27491 %= 16000;
            long c274901 = t27491 / 16000; t27491 %= 16000;
            long c274902 = t27491 / 16; t27491 %= 16;
            long c274903 = t27491;
            r943[i27489] = sub32(r941[c274900 * 16000 + c274902 * 16 + c274903 * 1], r942[c274900 * 1000 + c274902 * 1]);
        }
        /* max [max] -> r944 */
        for (long i27492 = 0; i27492 < 80000; ++i27492) {
            r944[i27492] = max32(r943[i27492], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r945 */
        for (long i27493 = 0; i27493 < 5000; ++i27493) {
            r945[i27493] = 0;
        }
        for (long i27494 = 0; i27494 < 80000; ++i27494) {
            long t27496 = i27494;
            long c274950 = t27496 / 16000; t27496 %= 16000;
            long c274951 = t27496 / 16000; t27496 %= 16000;
            long c274952 = t27496 / 16; t27496 %= 16;
            long c274953 = t27496;
            r945[c274950 * 1000 + c274951 * 1000 + c274952 * 1] = add32(r945[c274950 * 1000 + c274951 * 1000 + c274952 * 1], r944[i27494]);
        }
        /* add [add] -> r946 */
        for (long i27497 = 0; i27497 < 5000; ++i27497) {
            r946[i27497] = add32(r940[i27497], r945[i27497]);
        }
        /* gt [gt] -> r947 */
        for (long i27498 = 0; i27498 < 5000; ++i27498) {
            r947[i27498] = r946[i27498] > r930[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r948 */
        for (long i27499 = 0; i27499 < 5000; ++i27499) {
            r948[i27499] = r947[i27499] == 0 ? r932[i27499] : (r936[i27499]);
        }
        /* select_n [select_n] -> r949 */
        for (long i27500 = 0; i27500 < 5000; ++i27500) {
            r949[i27500] = r947[i27500] == 0 ? r936[i27500] : (r933[i27500]);
        }
        memcpy(r931, r934, sizeof(int32_t) * 1);
        memcpy(r932, r948, sizeof(int32_t) * 5000);
        memcpy(r933, r949, sizeof(int32_t) * 5000);
    }
    memcpy(r950, r931, sizeof(int32_t) * 1);
    memcpy(r951, r932, sizeof(int32_t) * 5000);
    memcpy(r952, r933, sizeof(int32_t) * 5000);
    /* sub [sub] -> r953 */
    for (long i27501 = 0; i27501 < 5000; ++i27501) {
        r953[i27501] = sub32(r925[i27501], r952[i27501]);
    }
    /* transpose [transpose] -> r954 */
    for (long i27502 = 0; i27502 < 5000; ++i27502) {
        long t27504 = i27502;
        long c275030 = t27504 / 5000; t27504 %= 5000;
        long c275031 = t27504 / 1000; t27504 %= 1000;
        long c275032 = t27504;
        r954[i27502] = r953[c275030 * 1000 + c275031 * 1000 + c275032 * 1];
    }
    /* max [max] -> r955 */
    for (long i27505 = 0; i27505 < 5000; ++i27505) {
        r955[i27505] = max32(r954[i27505], r13[0]);
    }
    /* reduce_sum [reduce_sum] -> r956 */
    for (long i27506 = 0; i27506 < 5; ++i27506) {
        r956[i27506] = 0;
    }
    for (long i27507 = 0; i27507 < 5000; ++i27507) {
        long t27509 = i27507;
        long c275080 = t27509 / 5000; t27509 %= 5000;
        long c275081 = t27509 / 1000; t27509 %= 1000;
        long c275082 = t27509;
        r956[c275080 * 5 + c275081 * 1] = add32(r956[c275080 * 5 + c275081 * 1], r955[i27507]);
    }
    /* shl [shift_left] -> r958 */
    for (long i27510 = 0; i27510 < 5; ++i27510) {
        r958[i27510] = shl32(r956[i27510], 4);
    }
    /* shl [shift_left] -> r959 */
    for (long i27511 = 0; i27511 < 1000; ++i27511) {
        r959[i27511] = shl32(r871[i27511], 1);
    }
    /* rev [rev] -> r960 */
    for (long i27512 = 0; i27512 < 6; ++i27512) {
        long t27514 = i27512;
        long c275130 = t27514 / 6; t27514 %= 6;
        long c275131 = t27514;
        r960[i27512] = r2[c275130 * 6 + (6 - 1 - c275131) * 1];
    }
    /* reshape [reshape] -> r961 */
    memcpy(r961, r960, sizeof(int32_t) * 6);
    /* convert [convert_element_type] -> r962 */
    for (long i27515 = 0; i27515 < 1; ++i27515) {
        r962[i27515] = (int32_t)r13[0];
    }
    /* pad [pad] -> r963 */
    for (long i27516 = 0; i27516 < 1005; ++i27516) {
        r963[i27516] = r962[0];
    }
    for (long i27517 = 0; i27517 < 1000; ++i27517) {
        long t27519 = i27517;
        long c275180 = t27519 / 1000; t27519 %= 1000;
        long c275181 = t27519;
        long d27520 = 0 + c275180 * 1;
        long d27521 = 5 + c275181 * 1;
        if (d27520 >= 0 && d27520 < 1 && d27521 >= 0 && d27521 < 1005) r963[d27520 * 1005 + d27521 * 1] = r959[i27517];
    }
    /* iota [iota] -> r964 */
    for (long i27522 = 0; i27522 < 1000; ++i27522) {
        long t27524 = i27522;
        long c275230 = t27524;
        r964[i27522] = (int32_t)c275230;
    }
    /* broadcast [broadcast_in_dim] -> r965 */
    for (long i27525 = 0; i27525 < 1000; ++i27525) {
        long t27527 = i27525;
        long c275260 = t27527 / 1; t27527 %= 1;
        long c275261 = t27527;
        r965[i27525] = r964[c275260 * 1];
    }
    /* iota [iota] -> r966 */
    for (long i27528 = 0; i27528 < 6; ++i27528) {
        long t27530 = i27528;
        long c275290 = t27530;
        r966[i27528] = (int32_t)c275290;
    }
    /* broadcast [broadcast_in_dim] -> r967 */
    for (long i27531 = 0; i27531 < 6; ++i27531) {
        long t27533 = i27531;
        long c275320 = t27533 / 6; t27533 %= 6;
        long c275321 = t27533;
        r967[i27531] = r966[c275321 * 1];
    }
    /* add [add] -> r968 */
    for (long i27534 = 0; i27534 < 6000; ++i27534) {
        long t27536 = i27534;
        long c275350 = t27536 / 6; t27536 %= 6;
        long c275351 = t27536;
        r968[i27534] = add32(r965[c275350 * 1], r967[c275351 * 1]);
    }
    /* lt [lt] -> r969 */
    for (long i27537 = 0; i27537 < 6000; ++i27537) {
        r969[i27537] = r968[i27537] < r13[0] ? 1 : 0;
    }
    /* add [add] -> r971 */
    for (long i27538 = 0; i27538 < 6000; ++i27538) {
        r971[i27538] = add32(r968[i27538], r970[0]);
    }
    /* select_n [select_n] -> r972 */
    for (long i27539 = 0; i27539 < 6000; ++i27539) {
        r972[i27539] = r969[i27539] == 0 ? r968[i27539] : (r971[i27539]);
    }
    /* broadcast [broadcast_in_dim] -> r973 */
    for (long i27540 = 0; i27540 < 6000; ++i27540) {
        long t27542 = i27540;
        long c275410 = t27542 / 6; t27542 %= 6;
        long c275411 = t27542 / 1; t27542 %= 1;
        long c275412 = t27542;
        r973[i27540] = r972[c275410 * 6 + c275411 * 1];
    }
    /* gather [gather] -> r974 */
    for (long i27543 = 0; i27543 < 6000; ++i27543) {
        long t27545 = i27543;
        long c275440 = t27545 / 6000; t27545 %= 6000;
        long c275441 = t27545 / 6; t27545 %= 6;
        long c275442 = t27545;
        long row27546 = c275441 * 6 + c275442 * 1;
        long s27547 = clamp_start((long)r973[row27546 + 0], 1005, 1);
        r974[i27543] = r963[c275440 * 1005 + s27547 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r975 */
    for (long i27548 = 0; i27548 < 6000; ++i27548) {
        long t27550 = i27548;
        long c275490 = t27550 / 6000; t27550 %= 6000;
        long c275491 = t27550 / 6000; t27550 %= 6000;
        long c275492 = t27550 / 6; t27550 %= 6;
        long c275493 = t27550;
        r975[i27548] = r974[c275492 * 6 + c275493 * 1];
    }
    /* add [add] -> r976 */
    for (long i27551 = 0; i27551 < 6000; ++i27551) {
        long t27553 = i27551;
        long c275520 = t27553 / 6000; t27553 %= 6000;
        long c275521 = t27553 / 6000; t27553 %= 6000;
        long c275522 = t27553 / 6; t27553 %= 6;
        long c275523 = t27553;
        r976[i27551] = add32(r961[c275523 * 1], r975[c275522 * 6 + c275523 * 1]);
    }
    /* convert [convert_element_type] -> r977 */
    for (long i27554 = 0; i27554 < 1; ++i27554) {
        r977[i27554] = (int32_t)r42[0];
    }
    /* max [max] -> r978 */
    for (long i27555 = 0; i27555 < 6000; ++i27555) {
        r978[i27555] = max32(r977[0], r976[i27555]);
    }
    /* convert [convert_element_type] -> r979 */
    for (long i27556 = 0; i27556 < 1; ++i27556) {
        r979[i27556] = (int32_t)r43[0];
    }
    /* min [min] -> r980 */
    for (long i27557 = 0; i27557 < 6000; ++i27557) {
        r980[i27557] = min32(r979[0], r978[i27557]);
    }
    /* sub [sub] -> r981 */
    for (long i27558 = 0; i27558 < 6000; ++i27558) {
        long t27560 = i27558;
        long c275590 = t27560 / 6000; t27560 %= 6000;
        long c275591 = t27560 / 6000; t27560 %= 6000;
        long c275592 = t27560 / 6; t27560 %= 6;
        long c275593 = t27560;
        r981[i27558] = sub32(r961[c275593 * 1], r975[c275592 * 6 + c275593 * 1]);
    }
    /* convert [convert_element_type] -> r982 */
    for (long i27561 = 0; i27561 < 1; ++i27561) {
        r982[i27561] = (int32_t)r42[0];
    }
    /* max [max] -> r983 */
    for (long i27562 = 0; i27562 < 6000; ++i27562) {
        r983[i27562] = max32(r982[0], r981[i27562]);
    }
    /* convert [convert_element_type] -> r984 */
    for (long i27563 = 0; i27563 < 1; ++i27563) {
        r984[i27563] = (int32_t)r43[0];
    }
    /* min [min] -> r985 */
    for (long i27564 = 0; i27564 < 6000; ++i27564) {
        r985[i27564] = min32(r984[0], r983[i27564]);
    }
    /* abs [abs] -> r986 */
    for (long i27565 = 0; i27565 < 6000; ++i27565) {
        r986[i27565] = abs32(r980[i27565]);
    }
    /* reduce_max [reduce_max] -> r987 */
    for (long i27566 = 0; i27566 < 1000; ++i27566) {
        r987[i27566] = (-2147483647 - 1);
    }
    for (long i27567 = 0; i27567 < 6000; ++i27567) {
        long t27569 = i27567;
        long c275680 = t27569 / 6000; t27569 %= 6000;
        long c275681 = t27569 / 6000; t27569 %= 6000;
        long c275682 = t27569 / 6; t27569 %= 6;
        long c275683 = t27569;
        r987[c275680 * 1000 + c275681 * 1000 + c275682 * 1] = max32(r987[c275680 * 1000 + c275681 * 1000 + c275682 * 1], r986[i27567]);
    }
    /* sub [sub] -> r988 */
    for (long i27570 = 0; i27570 < 1000; ++i27570) {
        r988[i27570] = sub32(r987[i27570], r55[0]);
    }
    /* loop [scan] -> r1010 */
    memcpy(r989, r980, sizeof(int32_t) * 6000);
    memcpy(r990, r55, sizeof(int32_t) * 1);
    memcpy(r991, r13, sizeof(int32_t) * 1);
    memcpy(r992, r988, sizeof(int32_t) * 1000);
    memcpy(r993, r987, sizeof(int32_t) * 1000);
    for (long t27571 = 0; t27571 < 12; ++t27571) {
        /* add [add] -> r994 */
        for (long i28572 = 0; i28572 < 1; ++i28572) {
            r994[i28572] = add32(r991[0], r9[0]);
        }
        /* add [add] -> r995 */
        for (long i28573 = 0; i28573 < 1000; ++i28573) {
            r995[i28573] = add32(r992[i28573], r993[i28573]);
        }
        /* shra [shift_right_arithmetic] -> r996 */
        for (long i28574 = 0; i28574 < 1000; ++i28574) {
            r996[i28574] = asr32(r995[i28574], 1);
        }
        /* broadcast [broadcast_in_dim] -> r997 */
        for (long i28575 = 0; i28575 < 1000; ++i28575) {
            long t28577 = i28575;
            long c285760 = t28577 / 1000; t28577 %= 1000;
            long c285761 = t28577 / 1000; t28577 %= 1000;
            long c285762 = t28577 / 1; t28577 %= 1;
            long c285763 = t28577;
            r997[i28575] = r996[c285762 * 1];
        }
        /* sub [sub] -> r998 */
        for (long i28578 = 0; i28578 < 6000; ++i28578) {
            long t28580 = i28578;
            long c285790 = t28580 / 6000; t28580 %= 6000;
            long c285791 = t28580 / 6000; t28580 %= 6000;
            long c285792 = t28580 / 6; t28580 %= 6;
            long c285793 = t28580;
            r998[i28578] = sub32(r989[c285792 * 6 + c285793 * 1], r997[c285792 * 1]);
        }
        /* max [max] -> r999 */
        for (long i28581 = 0; i28581 < 6000; ++i28581) {
            r999[i28581] = max32(r998[i28581], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1000 */
        for (long i28582 = 0; i28582 < 1000; ++i28582) {
            r1000[i28582] = 0;
        }
        for (long i28583 = 0; i28583 < 6000; ++i28583) {
            long t28585 = i28583;
            long c285840 = t28585 / 6000; t28585 %= 6000;
            long c285841 = t28585 / 6000; t28585 %= 6000;
            long c285842 = t28585 / 6; t28585 %= 6;
            long c285843 = t28585;
            r1000[c285840 * 1000 + c285841 * 1000 + c285842 * 1] = add32(r1000[c285840 * 1000 + c285841 * 1000 + c285842 * 1], r999[i28583]);
        }
        /* neg [neg] -> r1001 */
        for (long i28586 = 0; i28586 < 6000; ++i28586) {
            r1001[i28586] = neg32(r989[i28586]);
        }
        /* broadcast [broadcast_in_dim] -> r1002 */
        for (long i28587 = 0; i28587 < 1000; ++i28587) {
            long t28589 = i28587;
            long c285880 = t28589 / 1000; t28589 %= 1000;
            long c285881 = t28589 / 1000; t28589 %= 1000;
            long c285882 = t28589 / 1; t28589 %= 1;
            long c285883 = t28589;
            r1002[i28587] = r996[c285882 * 1];
        }
        /* sub [sub] -> r1003 */
        for (long i28590 = 0; i28590 < 6000; ++i28590) {
            long t28592 = i28590;
            long c285910 = t28592 / 6000; t28592 %= 6000;
            long c285911 = t28592 / 6000; t28592 %= 6000;
            long c285912 = t28592 / 6; t28592 %= 6;
            long c285913 = t28592;
            r1003[i28590] = sub32(r1001[c285912 * 6 + c285913 * 1], r1002[c285912 * 1]);
        }
        /* max [max] -> r1004 */
        for (long i28593 = 0; i28593 < 6000; ++i28593) {
            r1004[i28593] = max32(r1003[i28593], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1005 */
        for (long i28594 = 0; i28594 < 1000; ++i28594) {
            r1005[i28594] = 0;
        }
        for (long i28595 = 0; i28595 < 6000; ++i28595) {
            long t28597 = i28595;
            long c285960 = t28597 / 6000; t28597 %= 6000;
            long c285961 = t28597 / 6000; t28597 %= 6000;
            long c285962 = t28597 / 6; t28597 %= 6;
            long c285963 = t28597;
            r1005[c285960 * 1000 + c285961 * 1000 + c285962 * 1] = add32(r1005[c285960 * 1000 + c285961 * 1000 + c285962 * 1], r1004[i28595]);
        }
        /* add [add] -> r1006 */
        for (long i28598 = 0; i28598 < 1000; ++i28598) {
            r1006[i28598] = add32(r1000[i28598], r1005[i28598]);
        }
        /* gt [gt] -> r1007 */
        for (long i28599 = 0; i28599 < 1000; ++i28599) {
            r1007[i28599] = r1006[i28599] > r990[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1008 */
        for (long i28600 = 0; i28600 < 1000; ++i28600) {
            r1008[i28600] = r1007[i28600] == 0 ? r992[i28600] : (r996[i28600]);
        }
        /* select_n [select_n] -> r1009 */
        for (long i28601 = 0; i28601 < 1000; ++i28601) {
            r1009[i28601] = r1007[i28601] == 0 ? r996[i28601] : (r993[i28601]);
        }
        memcpy(r991, r994, sizeof(int32_t) * 1);
        memcpy(r992, r1008, sizeof(int32_t) * 1000);
        memcpy(r993, r1009, sizeof(int32_t) * 1000);
    }
    memcpy(r1010, r991, sizeof(int32_t) * 1);
    memcpy(r1011, r992, sizeof(int32_t) * 1000);
    memcpy(r1012, r993, sizeof(int32_t) * 1000);
    /* abs [abs] -> r1013 */
    for (long i28602 = 0; i28602 < 6000; ++i28602) {
        r1013[i28602] = abs32(r985[i28602]);
    }
    /* reduce_max [reduce_max] -> r1014 */
    for (long i28603 = 0; i28603 < 1000; ++i28603) {
        r1014[i28603] = (-2147483647 - 1);
    }
    for (long i28604 = 0; i28604 < 6000; ++i28604) {
        long t28606 = i28604;
        long c286050 = t28606 / 6000; t28606 %= 6000;
        long c286051 = t28606 / 6000; t28606 %= 6000;
        long c286052 = t28606 / 6; t28606 %= 6;
        long c286053 = t28606;
        r1014[c286050 * 1000 + c286051 * 1000 + c286052 * 1] = max32(r1014[c286050 * 1000 + c286051 * 1000 + c286052 * 1], r1013[i28604]);
    }
    /* sub [sub] -> r1015 */
    for (long i28607 = 0; i28607 < 1000; ++i28607) {
        r1015[i28607] = sub32(r1014[i28607], r55[0]);
    }
    /* loop [scan] -> r1037 */
    memcpy(r1016, r985, sizeof(int32_t) * 6000);
    memcpy(r1017, r55, sizeof(int32_t) * 1);
    memcpy(r1018, r13, sizeof(int32_t) * 1);
    memcpy(r1019, r1015, sizeof(int32_t) * 1000);
    memcpy(r1020, r1014, sizeof(int32_t) * 1000);
    for (long t28608 = 0; t28608 < 12; ++t28608) {
        /* add [add] -> r1021 */
        for (long i29609 = 0; i29609 < 1; ++i29609) {
            r1021[i29609] = add32(r1018[0], r9[0]);
        }
        /* add [add] -> r1022 */
        for (long i29610 = 0; i29610 < 1000; ++i29610) {
            r1022[i29610] = add32(r1019[i29610], r1020[i29610]);
        }
        /* shra [shift_right_arithmetic] -> r1023 */
        for (long i29611 = 0; i29611 < 1000; ++i29611) {
            r1023[i29611] = asr32(r1022[i29611], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1024 */
        for (long i29612 = 0; i29612 < 1000; ++i29612) {
            long t29614 = i29612;
            long c296130 = t29614 / 1000; t29614 %= 1000;
            long c296131 = t29614 / 1000; t29614 %= 1000;
            long c296132 = t29614 / 1; t29614 %= 1;
            long c296133 = t29614;
            r1024[i29612] = r1023[c296132 * 1];
        }
        /* sub [sub] -> r1025 */
        for (long i29615 = 0; i29615 < 6000; ++i29615) {
            long t29617 = i29615;
            long c296160 = t29617 / 6000; t29617 %= 6000;
            long c296161 = t29617 / 6000; t29617 %= 6000;
            long c296162 = t29617 / 6; t29617 %= 6;
            long c296163 = t29617;
            r1025[i29615] = sub32(r1016[c296162 * 6 + c296163 * 1], r1024[c296162 * 1]);
        }
        /* max [max] -> r1026 */
        for (long i29618 = 0; i29618 < 6000; ++i29618) {
            r1026[i29618] = max32(r1025[i29618], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1027 */
        for (long i29619 = 0; i29619 < 1000; ++i29619) {
            r1027[i29619] = 0;
        }
        for (long i29620 = 0; i29620 < 6000; ++i29620) {
            long t29622 = i29620;
            long c296210 = t29622 / 6000; t29622 %= 6000;
            long c296211 = t29622 / 6000; t29622 %= 6000;
            long c296212 = t29622 / 6; t29622 %= 6;
            long c296213 = t29622;
            r1027[c296210 * 1000 + c296211 * 1000 + c296212 * 1] = add32(r1027[c296210 * 1000 + c296211 * 1000 + c296212 * 1], r1026[i29620]);
        }
        /* neg [neg] -> r1028 */
        for (long i29623 = 0; i29623 < 6000; ++i29623) {
            r1028[i29623] = neg32(r1016[i29623]);
        }
        /* broadcast [broadcast_in_dim] -> r1029 */
        for (long i29624 = 0; i29624 < 1000; ++i29624) {
            long t29626 = i29624;
            long c296250 = t29626 / 1000; t29626 %= 1000;
            long c296251 = t29626 / 1000; t29626 %= 1000;
            long c296252 = t29626 / 1; t29626 %= 1;
            long c296253 = t29626;
            r1029[i29624] = r1023[c296252 * 1];
        }
        /* sub [sub] -> r1030 */
        for (long i29627 = 0; i29627 < 6000; ++i29627) {
            long t29629 = i29627;
            long c296280 = t29629 / 6000; t29629 %= 6000;
            long c296281 = t29629 / 6000; t29629 %= 6000;
            long c296282 = t29629 / 6; t29629 %= 6;
            long c296283 = t29629;
            r1030[i29627] = sub32(r1028[c296282 * 6 + c296283 * 1], r1029[c296282 * 1]);
        }
        /* max [max] -> r1031 */
        for (long i29630 = 0; i29630 < 6000; ++i29630) {
            r1031[i29630] = max32(r1030[i29630], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1032 */
        for (long i29631 = 0; i29631 < 1000; ++i29631) {
            r1032[i29631] = 0;
        }
        for (long i29632 = 0; i29632 < 6000; ++i29632) {
            long t29634 = i29632;
            long c296330 = t29634 / 6000; t29634 %= 6000;
            long c296331 = t29634 / 6000; t29634 %= 6000;
            long c296332 = t29634 / 6; t29634 %= 6;
            long c296333 = t29634;
            r1032[c296330 * 1000 + c296331 * 1000 + c296332 * 1] = add32(r1032[c296330 * 1000 + c296331 * 1000 + c296332 * 1], r1031[i29632]);
        }
        /* add [add] -> r1033 */
        for (long i29635 = 0; i29635 < 1000; ++i29635) {
            r1033[i29635] = add32(r1027[i29635], r1032[i29635]);
        }
        /* gt [gt] -> r1034 */
        for (long i29636 = 0; i29636 < 1000; ++i29636) {
            r1034[i29636] = r1033[i29636] > r1017[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1035 */
        for (long i29637 = 0; i29637 < 1000; ++i29637) {
            r1035[i29637] = r1034[i29637] == 0 ? r1019[i29637] : (r1023[i29637]);
        }
        /* select_n [select_n] -> r1036 */
        for (long i29638 = 0; i29638 < 1000; ++i29638) {
            r1036[i29638] = r1034[i29638] == 0 ? r1023[i29638] : (r1020[i29638]);
        }
        memcpy(r1018, r1021, sizeof(int32_t) * 1);
        memcpy(r1019, r1035, sizeof(int32_t) * 1000);
        memcpy(r1020, r1036, sizeof(int32_t) * 1000);
    }
    memcpy(r1037, r1018, sizeof(int32_t) * 1);
    memcpy(r1038, r1019, sizeof(int32_t) * 1000);
    memcpy(r1039, r1020, sizeof(int32_t) * 1000);
    /* sub [sub] -> r1040 */
    for (long i29639 = 0; i29639 < 1000; ++i29639) {
        r1040[i29639] = sub32(r1012[i29639], r1039[i29639]);
    }
    /* transpose [transpose] -> r1041 */
    for (long i29640 = 0; i29640 < 1000; ++i29640) {
        long t29642 = i29640;
        long c296410 = t29642 / 1000; t29642 %= 1000;
        long c296411 = t29642 / 1000; t29642 %= 1000;
        long c296412 = t29642;
        r1041[i29640] = r1040[c296410 * 1000 + c296411 * 1000 + c296412 * 1];
    }
    /* slice [slice] -> r1042 */
    for (long i29643 = 0; i29643 < 1000; ++i29643) {
        long t29645 = i29643;
        long c296440 = t29645 / 1000; t29645 %= 1000;
        long c296441 = t29645 / 1000; t29645 %= 1000;
        long c296442 = t29645;
        r1042[i29643] = r1041[(0 + c296440 * 1) * 1000 + (0 + c296441 * 1) * 1000 + (0 + c296442 * 1) * 1];
    }
    /* reshape [squeeze] -> r1043 */
    memcpy(r1043, r1042, sizeof(int32_t) * 1000);
    /* shra [shift_right_arithmetic] -> r1044 */
    for (long i29646 = 0; i29646 < 1000; ++i29646) {
        r1044[i29646] = asr32(r1043[i29646], 1);
    }
    /* convert [convert_element_type] -> r1045 */
    for (long i29647 = 0; i29647 < 1; ++i29647) {
        r1045[i29647] = (int32_t)r220[0];
    }
    /* max [max] -> r1046 */
    for (long i29648 = 0; i29648 < 1000; ++i29648) {
        r1046[i29648] = max32(r1045[0], r1044[i29648]);
    }
    /* convert [convert_element_type] -> r1047 */
    for (long i29649 = 0; i29649 < 1; ++i29649) {
        r1047[i29649] = (int32_t)r221[0];
    }
    /* min [min] -> r1048 */
    for (long i29650 = 0; i29650 < 1000; ++i29650) {
        r1048[i29650] = min32(r1047[0], r1046[i29650]);
    }
    /* iota [iota] -> r1049 */
    for (long i29651 = 0; i29651 < 500; ++i29651) {
        long t29653 = i29651;
        long c296520 = t29653;
        r1049[i29651] = (int32_t)c296520;
    }
    /* shl [mul] -> r1050 */
    for (long i29654 = 0; i29654 < 500; ++i29654) {
        r1050[i29654] = shl32(r1049[i29654], 1);
    }
    /* add [add] -> r1051 */
    for (long i29655 = 0; i29655 < 500; ++i29655) {
        r1051[i29655] = add32(r13[0], r1050[i29655]);
    }
    /* broadcast [broadcast_in_dim] -> r1052 */
    for (long i29656 = 0; i29656 < 500; ++i29656) {
        long t29658 = i29656;
        long c296570 = t29658 / 1; t29658 %= 1;
        long c296571 = t29658;
        r1052[i29656] = r1051[c296570 * 1];
    }
    /* gather [gather] -> r1053 */
    for (long i29659 = 0; i29659 < 500; ++i29659) {
        long t29661 = i29659;
        long c296600 = t29661 / 500; t29661 %= 500;
        long c296601 = t29661;
        long row29662 = c296601 * 1;
        long s29663 = clamp_start((long)r1052[row29662 + 0], 1000, 1);
        r1053[i29659] = r1048[c296600 * 1000 + s29663 * 1];
    }
    /* shl [shift_left] -> r1054 */
    for (long i29664 = 0; i29664 < 500; ++i29664) {
        r1054[i29664] = shl32(r1053[i29664], 1);
    }
    /* rev [rev] -> r1055 */
    for (long i29665 = 0; i29665 < 80; ++i29665) {
        long t29667 = i29665;
        long c296660 = t29667 / 16; t29667 %= 16;
        long c296661 = t29667;
        r1055[i29665] = r1[c296660 * 16 + (16 - 1 - c296661) * 1];
    }
    /* reshape [reshape] -> r1056 */
    memcpy(r1056, r1055, sizeof(int32_t) * 80);
    /* convert [convert_element_type] -> r1057 */
    for (long i29668 = 0; i29668 < 1; ++i29668) {
        r1057[i29668] = (int32_t)r13[0];
    }
    /* pad [pad] -> r1058 */
    for (long i29669 = 0; i29669 < 515; ++i29669) {
        r1058[i29669] = r1057[0];
    }
    for (long i29670 = 0; i29670 < 500; ++i29670) {
        long t29672 = i29670;
        long c296710 = t29672 / 500; t29672 %= 500;
        long c296711 = t29672;
        long d29673 = 0 + c296710 * 1;
        long d29674 = 15 + c296711 * 1;
        if (d29673 >= 0 && d29673 < 1 && d29674 >= 0 && d29674 < 515) r1058[d29673 * 515 + d29674 * 1] = r1054[i29670];
    }
    /* iota [iota] -> r1059 */
    for (long i29675 = 0; i29675 < 500; ++i29675) {
        long t29677 = i29675;
        long c296760 = t29677;
        r1059[i29675] = (int32_t)c296760;
    }
    /* broadcast [broadcast_in_dim] -> r1060 */
    for (long i29678 = 0; i29678 < 500; ++i29678) {
        long t29680 = i29678;
        long c296790 = t29680 / 1; t29680 %= 1;
        long c296791 = t29680;
        r1060[i29678] = r1059[c296790 * 1];
    }
    /* iota [iota] -> r1061 */
    for (long i29681 = 0; i29681 < 16; ++i29681) {
        long t29683 = i29681;
        long c296820 = t29683;
        r1061[i29681] = (int32_t)c296820;
    }
    /* broadcast [broadcast_in_dim] -> r1062 */
    for (long i29684 = 0; i29684 < 16; ++i29684) {
        long t29686 = i29684;
        long c296850 = t29686 / 16; t29686 %= 16;
        long c296851 = t29686;
        r1062[i29684] = r1061[c296851 * 1];
    }
    /* add [add] -> r1063 */
    for (long i29687 = 0; i29687 < 8000; ++i29687) {
        long t29689 = i29687;
        long c296880 = t29689 / 16; t29689 %= 16;
        long c296881 = t29689;
        r1063[i29687] = add32(r1060[c296880 * 1], r1062[c296881 * 1]);
    }
    /* lt [lt] -> r1064 */
    for (long i29690 = 0; i29690 < 8000; ++i29690) {
        r1064[i29690] = r1063[i29690] < r13[0] ? 1 : 0;
    }
    /* add [add] -> r1066 */
    for (long i29691 = 0; i29691 < 8000; ++i29691) {
        r1066[i29691] = add32(r1063[i29691], r1065[0]);
    }
    /* select_n [select_n] -> r1067 */
    for (long i29692 = 0; i29692 < 8000; ++i29692) {
        r1067[i29692] = r1064[i29692] == 0 ? r1063[i29692] : (r1066[i29692]);
    }
    /* broadcast [broadcast_in_dim] -> r1068 */
    for (long i29693 = 0; i29693 < 8000; ++i29693) {
        long t29695 = i29693;
        long c296940 = t29695 / 16; t29695 %= 16;
        long c296941 = t29695 / 1; t29695 %= 1;
        long c296942 = t29695;
        r1068[i29693] = r1067[c296940 * 16 + c296941 * 1];
    }
    /* gather [gather] -> r1069 */
    for (long i29696 = 0; i29696 < 8000; ++i29696) {
        long t29698 = i29696;
        long c296970 = t29698 / 8000; t29698 %= 8000;
        long c296971 = t29698 / 16; t29698 %= 16;
        long c296972 = t29698;
        long row29699 = c296971 * 16 + c296972 * 1;
        long s29700 = clamp_start((long)r1068[row29699 + 0], 515, 1);
        r1069[i29696] = r1058[c296970 * 515 + s29700 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r1070 */
    for (long i29701 = 0; i29701 < 8000; ++i29701) {
        long t29703 = i29701;
        long c297020 = t29703 / 8000; t29703 %= 8000;
        long c297021 = t29703 / 8000; t29703 %= 8000;
        long c297022 = t29703 / 16; t29703 %= 16;
        long c297023 = t29703;
        r1070[i29701] = r1069[c297022 * 16 + c297023 * 1];
    }
    /* add [add] -> r1071 */
    for (long i29704 = 0; i29704 < 40000; ++i29704) {
        long t29706 = i29704;
        long c297050 = t29706 / 8000; t29706 %= 8000;
        long c297051 = t29706 / 8000; t29706 %= 8000;
        long c297052 = t29706 / 16; t29706 %= 16;
        long c297053 = t29706;
        r1071[i29704] = add32(r1056[c297050 * 16 + c297053 * 1], r1070[c297052 * 16 + c297053 * 1]);
    }
    /* convert [convert_element_type] -> r1072 */
    for (long i29707 = 0; i29707 < 1; ++i29707) {
        r1072[i29707] = (int32_t)r42[0];
    }
    /* max [max] -> r1073 */
    for (long i29708 = 0; i29708 < 40000; ++i29708) {
        r1073[i29708] = max32(r1072[0], r1071[i29708]);
    }
    /* convert [convert_element_type] -> r1074 */
    for (long i29709 = 0; i29709 < 1; ++i29709) {
        r1074[i29709] = (int32_t)r43[0];
    }
    /* min [min] -> r1075 */
    for (long i29710 = 0; i29710 < 40000; ++i29710) {
        r1075[i29710] = min32(r1074[0], r1073[i29710]);
    }
    /* sub [sub] -> r1076 */
    for (long i29711 = 0; i29711 < 40000; ++i29711) {
        long t29713 = i29711;
        long c297120 = t29713 / 8000; t29713 %= 8000;
        long c297121 = t29713 / 8000; t29713 %= 8000;
        long c297122 = t29713 / 16; t29713 %= 16;
        long c297123 = t29713;
        r1076[i29711] = sub32(r1056[c297120 * 16 + c297123 * 1], r1070[c297122 * 16 + c297123 * 1]);
    }
    /* convert [convert_element_type] -> r1077 */
    for (long i29714 = 0; i29714 < 1; ++i29714) {
        r1077[i29714] = (int32_t)r42[0];
    }
    /* max [max] -> r1078 */
    for (long i29715 = 0; i29715 < 40000; ++i29715) {
        r1078[i29715] = max32(r1077[0], r1076[i29715]);
    }
    /* convert [convert_element_type] -> r1079 */
    for (long i29716 = 0; i29716 < 1; ++i29716) {
        r1079[i29716] = (int32_t)r43[0];
    }
    /* min [min] -> r1080 */
    for (long i29717 = 0; i29717 < 40000; ++i29717) {
        r1080[i29717] = min32(r1079[0], r1078[i29717]);
    }
    /* abs [abs] -> r1081 */
    for (long i29718 = 0; i29718 < 40000; ++i29718) {
        r1081[i29718] = abs32(r1075[i29718]);
    }
    /* reduce_max [reduce_max] -> r1082 */
    for (long i29719 = 0; i29719 < 2500; ++i29719) {
        r1082[i29719] = (-2147483647 - 1);
    }
    for (long i29720 = 0; i29720 < 40000; ++i29720) {
        long t29722 = i29720;
        long c297210 = t29722 / 8000; t29722 %= 8000;
        long c297211 = t29722 / 8000; t29722 %= 8000;
        long c297212 = t29722 / 16; t29722 %= 16;
        long c297213 = t29722;
        r1082[c297210 * 500 + c297211 * 500 + c297212 * 1] = max32(r1082[c297210 * 500 + c297211 * 500 + c297212 * 1], r1081[i29720]);
    }
    /* sub [sub] -> r1083 */
    for (long i29723 = 0; i29723 < 2500; ++i29723) {
        r1083[i29723] = sub32(r1082[i29723], r55[0]);
    }
    /* loop [scan] -> r1105 */
    memcpy(r1084, r1075, sizeof(int32_t) * 40000);
    memcpy(r1085, r55, sizeof(int32_t) * 1);
    memcpy(r1086, r13, sizeof(int32_t) * 1);
    memcpy(r1087, r1083, sizeof(int32_t) * 2500);
    memcpy(r1088, r1082, sizeof(int32_t) * 2500);
    for (long t29724 = 0; t29724 < 12; ++t29724) {
        /* add [add] -> r1089 */
        for (long i30725 = 0; i30725 < 1; ++i30725) {
            r1089[i30725] = add32(r1086[0], r9[0]);
        }
        /* add [add] -> r1090 */
        for (long i30726 = 0; i30726 < 2500; ++i30726) {
            r1090[i30726] = add32(r1087[i30726], r1088[i30726]);
        }
        /* shra [shift_right_arithmetic] -> r1091 */
        for (long i30727 = 0; i30727 < 2500; ++i30727) {
            r1091[i30727] = asr32(r1090[i30727], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1092 */
        for (long i30728 = 0; i30728 < 2500; ++i30728) {
            long t30730 = i30728;
            long c307290 = t30730 / 500; t30730 %= 500;
            long c307291 = t30730 / 500; t30730 %= 500;
            long c307292 = t30730 / 1; t30730 %= 1;
            long c307293 = t30730;
            r1092[i30728] = r1091[c307290 * 500 + c307292 * 1];
        }
        /* sub [sub] -> r1093 */
        for (long i30731 = 0; i30731 < 40000; ++i30731) {
            long t30733 = i30731;
            long c307320 = t30733 / 8000; t30733 %= 8000;
            long c307321 = t30733 / 8000; t30733 %= 8000;
            long c307322 = t30733 / 16; t30733 %= 16;
            long c307323 = t30733;
            r1093[i30731] = sub32(r1084[c307320 * 8000 + c307322 * 16 + c307323 * 1], r1092[c307320 * 500 + c307322 * 1]);
        }
        /* max [max] -> r1094 */
        for (long i30734 = 0; i30734 < 40000; ++i30734) {
            r1094[i30734] = max32(r1093[i30734], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1095 */
        for (long i30735 = 0; i30735 < 2500; ++i30735) {
            r1095[i30735] = 0;
        }
        for (long i30736 = 0; i30736 < 40000; ++i30736) {
            long t30738 = i30736;
            long c307370 = t30738 / 8000; t30738 %= 8000;
            long c307371 = t30738 / 8000; t30738 %= 8000;
            long c307372 = t30738 / 16; t30738 %= 16;
            long c307373 = t30738;
            r1095[c307370 * 500 + c307371 * 500 + c307372 * 1] = add32(r1095[c307370 * 500 + c307371 * 500 + c307372 * 1], r1094[i30736]);
        }
        /* neg [neg] -> r1096 */
        for (long i30739 = 0; i30739 < 40000; ++i30739) {
            r1096[i30739] = neg32(r1084[i30739]);
        }
        /* broadcast [broadcast_in_dim] -> r1097 */
        for (long i30740 = 0; i30740 < 2500; ++i30740) {
            long t30742 = i30740;
            long c307410 = t30742 / 500; t30742 %= 500;
            long c307411 = t30742 / 500; t30742 %= 500;
            long c307412 = t30742 / 1; t30742 %= 1;
            long c307413 = t30742;
            r1097[i30740] = r1091[c307410 * 500 + c307412 * 1];
        }
        /* sub [sub] -> r1098 */
        for (long i30743 = 0; i30743 < 40000; ++i30743) {
            long t30745 = i30743;
            long c307440 = t30745 / 8000; t30745 %= 8000;
            long c307441 = t30745 / 8000; t30745 %= 8000;
            long c307442 = t30745 / 16; t30745 %= 16;
            long c307443 = t30745;
            r1098[i30743] = sub32(r1096[c307440 * 8000 + c307442 * 16 + c307443 * 1], r1097[c307440 * 500 + c307442 * 1]);
        }
        /* max [max] -> r1099 */
        for (long i30746 = 0; i30746 < 40000; ++i30746) {
            r1099[i30746] = max32(r1098[i30746], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1100 */
        for (long i30747 = 0; i30747 < 2500; ++i30747) {
            r1100[i30747] = 0;
        }
        for (long i30748 = 0; i30748 < 40000; ++i30748) {
            long t30750 = i30748;
            long c307490 = t30750 / 8000; t30750 %= 8000;
            long c307491 = t30750 / 8000; t30750 %= 8000;
            long c307492 = t30750 / 16; t30750 %= 16;
            long c307493 = t30750;
            r1100[c307490 * 500 + c307491 * 500 + c307492 * 1] = add32(r1100[c307490 * 500 + c307491 * 500 + c307492 * 1], r1099[i30748]);
        }
        /* add [add] -> r1101 */
        for (long i30751 = 0; i30751 < 2500; ++i30751) {
            r1101[i30751] = add32(r1095[i30751], r1100[i30751]);
        }
        /* gt [gt] -> r1102 */
        for (long i30752 = 0; i30752 < 2500; ++i30752) {
            r1102[i30752] = r1101[i30752] > r1085[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1103 */
        for (long i30753 = 0; i30753 < 2500; ++i30753) {
            r1103[i30753] = r1102[i30753] == 0 ? r1087[i30753] : (r1091[i30753]);
        }
        /* select_n [select_n] -> r1104 */
        for (long i30754 = 0; i30754 < 2500; ++i30754) {
            r1104[i30754] = r1102[i30754] == 0 ? r1091[i30754] : (r1088[i30754]);
        }
        memcpy(r1086, r1089, sizeof(int32_t) * 1);
        memcpy(r1087, r1103, sizeof(int32_t) * 2500);
        memcpy(r1088, r1104, sizeof(int32_t) * 2500);
    }
    memcpy(r1105, r1086, sizeof(int32_t) * 1);
    memcpy(r1106, r1087, sizeof(int32_t) * 2500);
    memcpy(r1107, r1088, sizeof(int32_t) * 2500);
    /* abs [abs] -> r1108 */
    for (long i30755 = 0; i30755 < 40000; ++i30755) {
        r1108[i30755] = abs32(r1080[i30755]);
    }
    /* reduce_max [reduce_max] -> r1109 */
    for (long i30756 = 0; i30756 < 2500; ++i30756) {
        r1109[i30756] = (-2147483647 - 1);
    }
    for (long i30757 = 0; i30757 < 40000; ++i30757) {
        long t30759 = i30757;
        long c307580 = t30759 / 8000; t30759 %= 8000;
        long c307581 = t30759 / 8000; t30759 %= 8000;
        long c307582 = t30759 / 16; t30759 %= 16;
        long c307583 = t30759;
        r1109[c307580 * 500 + c307581 * 500 + c307582 * 1] = max32(r1109[c307580 * 500 + c307581 * 500 + c307582 * 1], r1108[i30757]);
    }
    /* sub [sub] -> r1110 */
    for (long i30760 = 0; i30760 < 2500; ++i30760) {
        r1110[i30760] = sub32(r1109[i30760], r55[0]);
    }
    /* loop [scan] -> r1132 */
    memcpy(r1111, r1080, sizeof(int32_t) * 40000);
    memcpy(r1112, r55, sizeof(int32_t) * 1);
    memcpy(r1113, r13, sizeof(int32_t) * 1);
    memcpy(r1114, r1110, sizeof(int32_t) * 2500);
    memcpy(r1115, r1109, sizeof(int32_t) * 2500);
    for (long t30761 = 0; t30761 < 12; ++t30761) {
        /* add [add] -> r1116 */
        for (long i31762 = 0; i31762 < 1; ++i31762) {
            r1116[i31762] = add32(r1113[0], r9[0]);
        }
        /* add [add] -> r1117 */
        for (long i31763 = 0; i31763 < 2500; ++i31763) {
            r1117[i31763] = add32(r1114[i31763], r1115[i31763]);
        }
        /* shra [shift_right_arithmetic] -> r1118 */
        for (long i31764 = 0; i31764 < 2500; ++i31764) {
            r1118[i31764] = asr32(r1117[i31764], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1119 */
        for (long i31765 = 0; i31765 < 2500; ++i31765) {
            long t31767 = i31765;
            long c317660 = t31767 / 500; t31767 %= 500;
            long c317661 = t31767 / 500; t31767 %= 500;
            long c317662 = t31767 / 1; t31767 %= 1;
            long c317663 = t31767;
            r1119[i31765] = r1118[c317660 * 500 + c317662 * 1];
        }
        /* sub [sub] -> r1120 */
        for (long i31768 = 0; i31768 < 40000; ++i31768) {
            long t31770 = i31768;
            long c317690 = t31770 / 8000; t31770 %= 8000;
            long c317691 = t31770 / 8000; t31770 %= 8000;
            long c317692 = t31770 / 16; t31770 %= 16;
            long c317693 = t31770;
            r1120[i31768] = sub32(r1111[c317690 * 8000 + c317692 * 16 + c317693 * 1], r1119[c317690 * 500 + c317692 * 1]);
        }
        /* max [max] -> r1121 */
        for (long i31771 = 0; i31771 < 40000; ++i31771) {
            r1121[i31771] = max32(r1120[i31771], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1122 */
        for (long i31772 = 0; i31772 < 2500; ++i31772) {
            r1122[i31772] = 0;
        }
        for (long i31773 = 0; i31773 < 40000; ++i31773) {
            long t31775 = i31773;
            long c317740 = t31775 / 8000; t31775 %= 8000;
            long c317741 = t31775 / 8000; t31775 %= 8000;
            long c317742 = t31775 / 16; t31775 %= 16;
            long c317743 = t31775;
            r1122[c317740 * 500 + c317741 * 500 + c317742 * 1] = add32(r1122[c317740 * 500 + c317741 * 500 + c317742 * 1], r1121[i31773]);
        }
        /* neg [neg] -> r1123 */
        for (long i31776 = 0; i31776 < 40000; ++i31776) {
            r1123[i31776] = neg32(r1111[i31776]);
        }
        /* broadcast [broadcast_in_dim] -> r1124 */
        for (long i31777 = 0; i31777 < 2500; ++i31777) {
            long t31779 = i31777;
            long c317780 = t31779 / 500; t31779 %= 500;
            long c317781 = t31779 / 500; t31779 %= 500;
            long c317782 = t31779 / 1; t31779 %= 1;
            long c317783 = t31779;
            r1124[i31777] = r1118[c317780 * 500 + c317782 * 1];
        }
        /* sub [sub] -> r1125 */
        for (long i31780 = 0; i31780 < 40000; ++i31780) {
            long t31782 = i31780;
            long c317810 = t31782 / 8000; t31782 %= 8000;
            long c317811 = t31782 / 8000; t31782 %= 8000;
            long c317812 = t31782 / 16; t31782 %= 16;
            long c317813 = t31782;
            r1125[i31780] = sub32(r1123[c317810 * 8000 + c317812 * 16 + c317813 * 1], r1124[c317810 * 500 + c317812 * 1]);
        }
        /* max [max] -> r1126 */
        for (long i31783 = 0; i31783 < 40000; ++i31783) {
            r1126[i31783] = max32(r1125[i31783], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1127 */
        for (long i31784 = 0; i31784 < 2500; ++i31784) {
            r1127[i31784] = 0;
        }
        for (long i31785 = 0; i31785 < 40000; ++i31785) {
            long t31787 = i31785;
            long c317860 = t31787 / 8000; t31787 %= 8000;
            long c317861 = t31787 / 8000; t31787 %= 8000;
            long c317862 = t31787 / 16; t31787 %= 16;
            long c317863 = t31787;
            r1127[c317860 * 500 + c317861 * 500 + c317862 * 1] = add32(r1127[c317860 * 500 + c317861 * 500 + c317862 * 1], r1126[i31785]);
        }
        /* add [add] -> r1128 */
        for (long i31788 = 0; i31788 < 2500; ++i31788) {
            r1128[i31788] = add32(r1122[i31788], r1127[i31788]);
        }
        /* gt [gt] -> r1129 */
        for (long i31789 = 0; i31789 < 2500; ++i31789) {
            r1129[i31789] = r1128[i31789] > r1112[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1130 */
        for (long i31790 = 0; i31790 < 2500; ++i31790) {
            r1130[i31790] = r1129[i31790] == 0 ? r1114[i31790] : (r1118[i31790]);
        }
        /* select_n [select_n] -> r1131 */
        for (long i31791 = 0; i31791 < 2500; ++i31791) {
            r1131[i31791] = r1129[i31791] == 0 ? r1118[i31791] : (r1115[i31791]);
        }
        memcpy(r1113, r1116, sizeof(int32_t) * 1);
        memcpy(r1114, r1130, sizeof(int32_t) * 2500);
        memcpy(r1115, r1131, sizeof(int32_t) * 2500);
    }
    memcpy(r1132, r1113, sizeof(int32_t) * 1);
    memcpy(r1133, r1114, sizeof(int32_t) * 2500);
    memcpy(r1134, r1115, sizeof(int32_t) * 2500);
    /* sub [sub] -> r1135 */
    for (long i31792 = 0; i31792 < 2500; ++i31792) {
        r1135[i31792] = sub32(r1107[i31792], r1134[i31792]);
    }
    /* transpose [transpose] -> r1136 */
    for (long i31793 = 0; i31793 < 2500; ++i31793) {
        long t31795 = i31793;
        long c317940 = t31795 / 2500; t31795 %= 2500;
        long c317941 = t31795 / 500; t31795 %= 500;
        long c317942 = t31795;
        r1136[i31793] = r1135[c317940 * 500 + c317941 * 500 + c317942 * 1];
    }
    /* max [max] -> r1137 */
    for (long i31796 = 0; i31796 < 2500; ++i31796) {
        r1137[i31796] = max32(r1136[i31796], r13[0]);
    }
    /* reduce_sum [reduce_sum] -> r1138 */
    for (long i31797 = 0; i31797 < 5; ++i31797) {
        r1138[i31797] = 0;
    }
    for (long i31798 = 0; i31798 < 2500; ++i31798) {
        long t31800 = i31798;
        long c317990 = t31800 / 2500; t31800 %= 2500;
        long c317991 = t31800 / 500; t31800 %= 500;
        long c317992 = t31800;
        r1138[c317990 * 5 + c317991 * 1] = add32(r1138[c317990 * 5 + c317991 * 1], r1137[i31798]);
    }
    /* shl [shift_left] -> r1140 */
    for (long i31801 = 0; i31801 < 5; ++i31801) {
        r1140[i31801] = shl32(r1138[i31801], 5);
    }
    /* concat [concatenate] -> r1141 */
    for (long i31802 = 0; i31802 < 5; ++i31802) {
        long t31804 = i31802;
        long c318030 = t31804 / 5; t31804 %= 5;
        long c318031 = t31804;
        r1141[c318030 * 30 + (c318031 + 0) * 1] = r116[i31802];
    }
    for (long i31805 = 0; i31805 < 5; ++i31805) {
        long t31807 = i31805;
        long c318060 = t31807 / 5; t31807 %= 5;
        long c318061 = t31807;
        r1141[c318060 * 30 + (c318061 + 5) * 1] = r332[i31805];
    }
    for (long i31808 = 0; i31808 < 5; ++i31808) {
        long t31810 = i31808;
        long c318090 = t31810 / 5; t31810 %= 5;
        long c318091 = t31810;
        r1141[c318090 * 30 + (c318091 + 10) * 1] = r546[i31808];
    }
    for (long i31811 = 0; i31811 < 5; ++i31811) {
        long t31813 = i31811;
        long c318120 = t31813 / 5; t31813 %= 5;
        long c318121 = t31813;
        r1141[c318120 * 30 + (c318121 + 15) * 1] = r760[i31811];
    }
    for (long i31814 = 0; i31814 < 5; ++i31814) {
        long t31816 = i31814;
        long c318150 = t31816 / 5; t31816 %= 5;
        long c318151 = t31816;
        r1141[c318150 * 30 + (c318151 + 20) * 1] = r958[i31814];
    }
    for (long i31817 = 0; i31817 < 5; ++i31817) {
        long t31819 = i31817;
        long c318180 = t31819 / 5; t31819 %= 5;
        long c318181 = t31819;
        r1141[c318180 * 30 + (c318181 + 25) * 1] = r1140[i31817];
    }
    /* broadcast [broadcast_in_dim] -> r1142 */
    for (long i31820 = 0; i31820 < 30; ++i31820) {
        long t31822 = i31820;
        long c318210 = t31822 / 30; t31822 %= 30;
        long c318211 = t31822;
        r1142[i31820] = r3[c318211 * 1];
    }
    /* sub [sub] -> r1143 */
    for (long i31823 = 0; i31823 < 30; ++i31823) {
        r1143[i31823] = sub32(r1141[i31823], r1142[i31823]);
    }
    /* ge [ge] -> r1144 */
    for (long i31824 = 0; i31824 < 30; ++i31824) {
        r1144[i31824] = r4[i31824] >= r13[0] ? 1 : 0;
    }
    /* max [max] -> r1145 */
    for (long i31825 = 0; i31825 < 30; ++i31825) {
        r1145[i31825] = max32(r4[i31825], r13[0]);
    }
    /* broadcast [broadcast_in_dim] -> r1146 */
    for (long i31826 = 0; i31826 < 30; ++i31826) {
        long t31828 = i31826;
        long c318270 = t31828 / 30; t31828 %= 30;
        long c318271 = t31828;
        r1146[i31826] = r1145[c318271 * 1];
    }
    /* shl [shift_left] -> r1147 */
    for (long i31829 = 0; i31829 < 30; ++i31829) {
        r1147[i31829] = shl32(r1143[i31829], r1146[i31829]);
    }
    /* neg [neg] -> r1148 */
    for (long i31830 = 0; i31830 < 30; ++i31830) {
        r1148[i31830] = neg32(r4[i31830]);
    }
    /* max [max] -> r1149 */
    for (long i31831 = 0; i31831 < 30; ++i31831) {
        r1149[i31831] = max32(r1148[i31831], r13[0]);
    }
    /* broadcast [broadcast_in_dim] -> r1150 */
    for (long i31832 = 0; i31832 < 30; ++i31832) {
        long t31834 = i31832;
        long c318330 = t31834 / 30; t31834 %= 30;
        long c318331 = t31834;
        r1150[i31832] = r1149[c318331 * 1];
    }
    /* shra [shift_right_arithmetic] -> r1151 */
    for (long i31835 = 0; i31835 < 30; ++i31835) {
        r1151[i31835] = asr32(r1143[i31835], r1150[i31835]);
    }
    /* broadcast [broadcast_in_dim] -> r1152 */
    for (long i31836 = 0; i31836 < 30; ++i31836) {
        long t31838 = i31836;
        long c318370 = t31838 / 30; t31838 %= 30;
        long c318371 = t31838;
        r1152[i31836] = r1144[c318371 * 1];
    }
    /* select_n [select_n] -> r1153 */
    for (long i31839 = 0; i31839 < 30; ++i31839) {
        r1153[i31839] = r1152[i31839] == 0 ? r1151[i31839] : (r1147[i31839]);
    }
    /* ge [ge] -> r1154 */
    for (long i31840 = 0; i31840 < 30; ++i31840) {
        r1154[i31840] = r5[i31840] >= r13[0] ? 1 : 0;
    }
    /* max [max] -> r1155 */
    for (long i31841 = 0; i31841 < 30; ++i31841) {
        r1155[i31841] = max32(r5[i31841], r13[0]);
    }
    /* broadcast [broadcast_in_dim] -> r1156 */
    for (long i31842 = 0; i31842 < 30; ++i31842) {
        long t31844 = i31842;
        long c318430 = t31844 / 30; t31844 %= 30;
        long c318431 = t31844;
        r1156[i31842] = r1155[c318431 * 1];
    }
    /* shl [shift_left] -> r1157 */
    for (long i31845 = 0; i31845 < 30; ++i31845) {
        r1157[i31845] = shl32(r1143[i31845], r1156[i31845]);
    }
    /* neg [neg] -> r1158 */
    for (long i31846 = 0; i31846 < 30; ++i31846) {
        r1158[i31846] = neg32(r5[i31846]);
    }
    /* max [max] -> r1159 */
    for (long i31847 = 0; i31847 < 30; ++i31847) {
        r1159[i31847] = max32(r1158[i31847], r13[0]);
    }
    /* broadcast [broadcast_in_dim] -> r1160 */
    for (long i31848 = 0; i31848 < 30; ++i31848) {
        long t31850 = i31848;
        long c318490 = t31850 / 30; t31850 %= 30;
        long c318491 = t31850;
        r1160[i31848] = r1159[c318491 * 1];
    }
    /* shra [shift_right_arithmetic] -> r1161 */
    for (long i31851 = 0; i31851 < 30; ++i31851) {
        r1161[i31851] = asr32(r1143[i31851], r1160[i31851]);
    }
    /* broadcast [broadcast_in_dim] -> r1162 */
    for (long i31852 = 0; i31852 < 30; ++i31852) {
        long t31854 = i31852;
        long c318530 = t31854 / 30; t31854 %= 30;
        long c318531 = t31854;
        r1162[i31852] = r1154[c318531 * 1];
    }
    /* select_n [select_n] -> r1163 */
    for (long i31855 = 0; i31855 < 30; ++i31855) {
        r1163[i31855] = r1162[i31855] == 0 ? r1161[i31855] : (r1157[i31855]);
    }
    /* gt [gt] -> r1164 */
    for (long i31856 = 0; i31856 < 30; ++i31856) {
        r1164[i31856] = r3[i31856] > r13[0] ? 1 : 0;
    }
    /* add [add] -> r1165 */
    for (long i31857 = 0; i31857 < 30; ++i31857) {
        r1165[i31857] = add32(r1153[i31857], r1163[i31857]);
    }
    /* lt [lt] -> r1166 */
    for (long i31858 = 0; i31858 < 30; ++i31858) {
        r1166[i31858] = r3[i31858] < r13[0] ? 1 : 0;
    }
    /* sub [sub] -> r1167 */
    for (long i31859 = 0; i31859 < 30; ++i31859) {
        r1167[i31859] = sub32(r1153[i31859], r1163[i31859]);
    }
    /* broadcast [broadcast_in_dim] -> r1168 */
    for (long i31860 = 0; i31860 < 30; ++i31860) {
        long t31862 = i31860;
        long c318610 = t31862 / 30; t31862 %= 30;
        long c318611 = t31862;
        r1168[i31860] = r1166[c318611 * 1];
    }
    /* select_n [select_n] -> r1169 */
    for (long i31863 = 0; i31863 < 30; ++i31863) {
        r1169[i31863] = r1168[i31863] == 0 ? r1153[i31863] : (r1167[i31863]);
    }
    /* broadcast [broadcast_in_dim] -> r1170 */
    for (long i31864 = 0; i31864 < 30; ++i31864) {
        long t31866 = i31864;
        long c318650 = t31866 / 30; t31866 %= 30;
        long c318651 = t31866;
        r1170[i31864] = r1164[c318651 * 1];
    }
    /* select_n [select_n] -> r1171 */
    for (long i31867 = 0; i31867 < 30; ++i31867) {
        r1171[i31867] = r1170[i31867] == 0 ? r1169[i31867] : (r1165[i31867]);
    }
    /* convert [convert_element_type] -> r1172 */
    for (long i31868 = 0; i31868 < 1; ++i31868) {
        r1172[i31868] = (int32_t)r220[0];
    }
    /* max [max] -> r1173 */
    for (long i31869 = 0; i31869 < 30; ++i31869) {
        r1173[i31869] = max32(r1172[0], r1171[i31869]);
    }
    /* convert [convert_element_type] -> r1174 */
    for (long i31870 = 0; i31870 < 1; ++i31870) {
        r1174[i31870] = (int32_t)r221[0];
    }
    /* min [min] -> r1175 */
    for (long i31871 = 0; i31871 < 30; ++i31871) {
        r1175[i31871] = min32(r1174[0], r1173[i31871]);
    }
    /* shl [shift_left] -> r1176 */
    for (long i31872 = 0; i31872 < 30; ++i31872) {
        r1176[i31872] = shl32(r1175[i31872], 1);
    }
    /* broadcast [broadcast_in_dim] -> r1177 */
    for (long i31873 = 0; i31873 < 30; ++i31873) {
        long t31875 = i31873;
        long c318740 = t31875 / 30; t31875 %= 30;
        long c318741 = t31875 / 1; t31875 %= 1;
        long c318742 = t31875;
        r1177[i31873] = r1176[c318741 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r1178 */
    for (long i31876 = 0; i31876 < 30; ++i31876) {
        long t31878 = i31876;
        long c318770 = t31878 / 30; t31878 %= 30;
        long c318771 = t31878 / 1; t31878 %= 1;
        long c318772 = t31878;
        r1178[i31876] = r1176[c318771 * 1];
    }
    /* neg [neg] -> r1179 */
    for (long i31879 = 0; i31879 < 30; ++i31879) {
        r1179[i31879] = neg32(r1178[i31879]);
    }
    /* broadcast [broadcast_in_dim] -> r1180 */
    for (long i31880 = 0; i31880 < 300; ++i31880) {
        long t31882 = i31880;
        long c318810 = t31882 / 300; t31882 %= 300;
        long c318811 = t31882 / 10; t31882 %= 10;
        long c318812 = t31882;
        r1180[i31880] = r6[c318811 * 10 + c318812 * 1];
    }
    /* add [add] -> r1181 */
    for (long i31883 = 0; i31883 < 300; ++i31883) {
        long t31885 = i31883;
        long c318840 = t31885 / 300; t31885 %= 300;
        long c318841 = t31885 / 10; t31885 %= 10;
        long c318842 = t31885;
        r1181[i31883] = add32(r1180[c318841 * 10 + c318842 * 1], r1177[c318841 * 1]);
    }
    /* convert [convert_element_type] -> r1182 */
    for (long i31886 = 0; i31886 < 1; ++i31886) {
        r1182[i31886] = (int32_t)r42[0];
    }
    /* max [max] -> r1183 */
    for (long i31887 = 0; i31887 < 300; ++i31887) {
        r1183[i31887] = max32(r1182[0], r1181[i31887]);
    }
    /* convert [convert_element_type] -> r1184 */
    for (long i31888 = 0; i31888 < 1; ++i31888) {
        r1184[i31888] = (int32_t)r43[0];
    }
    /* min [min] -> r1185 */
    for (long i31889 = 0; i31889 < 300; ++i31889) {
        r1185[i31889] = min32(r1184[0], r1183[i31889]);
    }
    /* broadcast [broadcast_in_dim] -> r1186 */
    for (long i31890 = 0; i31890 < 300; ++i31890) {
        long t31892 = i31890;
        long c318910 = t31892 / 300; t31892 %= 300;
        long c318911 = t31892 / 10; t31892 %= 10;
        long c318912 = t31892;
        r1186[i31890] = r7[c318911 * 10 + c318912 * 1];
    }
    /* add [add] -> r1187 */
    for (long i31893 = 0; i31893 < 300; ++i31893) {
        long t31895 = i31893;
        long c318940 = t31895 / 300; t31895 %= 300;
        long c318941 = t31895 / 10; t31895 %= 10;
        long c318942 = t31895;
        r1187[i31893] = add32(r1186[c318941 * 10 + c318942 * 1], r1179[c318941 * 1]);
    }
    /* convert [convert_element_type] -> r1188 */
    for (long i31896 = 0; i31896 < 1; ++i31896) {
        r1188[i31896] = (int32_t)r42[0];
    }
    /* max [max] -> r1189 */
    for (long i31897 = 0; i31897 < 300; ++i31897) {
        r1189[i31897] = max32(r1188[0], r1187[i31897]);
    }
    /* convert [convert_element_type] -> r1190 */
    for (long i31898 = 0; i31898 < 1; ++i31898) {
        r1190[i31898] = (int32_t)r43[0];
    }
    /* min [min] -> r1191 */
    for (long i31899 = 0; i31899 < 300; ++i31899) {
        r1191[i31899] = min32(r1190[0], r1189[i31899]);
    }
    /* concat [concatenate] -> r1192 */
    for (long i31900 = 0; i31900 < 300; ++i31900) {
        long t31902 = i31900;
        long c319010 = t31902 / 300; t31902 %= 300;
        long c319011 = t31902 / 10; t31902 %= 10;
        long c319012 = t31902;
        r1192[c319010 * 600 + (c319011 + 0) * 10 + c319012 * 1] = r1185[i31900];
    }
    for (long i31903 = 0; i31903 < 300; ++i31903) {
        long t31905 = i31903;
        long c319040 = t31905 / 300; t31905 %= 300;
        long c319041 = t31905 / 10; t31905 %= 10;
        long c319042 = t31905;
        r1192[c319040 * 600 + (c319041 + 30) * 10 + c319042 * 1] = r1191[i31903];
    }
    /* broadcast [broadcast_in_dim] -> r1193 */
    for (long i31906 = 0; i31906 < 10; ++i31906) {
        long t31908 = i31906;
        long c319070 = t31908 / 10; t31908 %= 10;
        long c319071 = t31908 / 10; t31908 %= 10;
        long c319072 = t31908;
        r1193[i31906] = r8[c319072 * 1];
    }
    /* concat [concatenate] -> r1194 */
    for (long i31909 = 0; i31909 < 600; ++i31909) {
        long t31911 = i31909;
        long c319100 = t31911 / 600; t31911 %= 600;
        long c319101 = t31911 / 10; t31911 %= 10;
        long c319102 = t31911;
        r1194[c319100 * 610 + (c319101 + 0) * 10 + c319102 * 1] = r1192[i31909];
    }
    for (long i31912 = 0; i31912 < 10; ++i31912) {
        long t31914 = i31912;
        long c319130 = t31914 / 10; t31914 %= 10;
        long c319131 = t31914 / 10; t31914 %= 10;
        long c319132 = t31914;
        r1194[c319130 * 610 + (c319131 + 60) * 10 + c319132 * 1] = r1193[i31912];
    }
    /* transpose [transpose] -> r1195 */
    for (long i31915 = 0; i31915 < 610; ++i31915) {
        long t31917 = i31915;
        long c319160 = t31917 / 610; t31917 %= 610;
        long c319161 = t31917 / 61; t31917 %= 61;
        long c319162 = t31917;
        r1195[i31915] = r1194[c319160 * 610 + c319161 * 1 + c319162 * 10];
    }
    /* reduce_max [reduce_max] -> r1196 */
    for (long i31918 = 0; i31918 < 10; ++i31918) {
        r1196[i31918] = (-2147483647 - 1);
    }
    for (long i31919 = 0; i31919 < 610; ++i31919) {
        long t31921 = i31919;
        long c319200 = t31921 / 610; t31921 %= 610;
        long c319201 = t31921 / 61; t31921 %= 61;
        long c319202 = t31921;
        r1196[c319200 * 10 + c319201 * 1] = max32(r1196[c319200 * 10 + c319201 * 1], r1195[i31919]);
    }
    /* sub [sub] -> r1198 */
    for (long i31922 = 0; i31922 < 10; ++i31922) {
        r1198[i31922] = sub32(r1196[i31922], r1197[0]);
    }
    /* loop [scan] -> r1214 */
    memcpy(r1199, r1195, sizeof(int32_t) * 610);
    memcpy(r1200, r1197, sizeof(int32_t) * 1);
    memcpy(r1201, r13, sizeof(int32_t) * 1);
    memcpy(r1202, r1198, sizeof(int32_t) * 10);
    memcpy(r1203, r1196, sizeof(int32_t) * 10);
    for (long t31923 = 0; t31923 < 11; ++t31923) {
        /* add [add] -> r1204 */
        for (long i32924 = 0; i32924 < 1; ++i32924) {
            r1204[i32924] = add32(r1201[0], r9[0]);
        }
        /* add [add] -> r1205 */
        for (long i32925 = 0; i32925 < 10; ++i32925) {
            r1205[i32925] = add32(r1202[i32925], r1203[i32925]);
        }
        /* shra [shift_right_arithmetic] -> r1206 */
        for (long i32926 = 0; i32926 < 10; ++i32926) {
            r1206[i32926] = asr32(r1205[i32926], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1207 */
        for (long i32927 = 0; i32927 < 10; ++i32927) {
            long t32929 = i32927;
            long c329280 = t32929 / 10; t32929 %= 10;
            long c329281 = t32929 / 1; t32929 %= 1;
            long c329282 = t32929;
            r1207[i32927] = r1206[c329281 * 1];
        }
        /* sub [sub] -> r1208 */
        for (long i32930 = 0; i32930 < 610; ++i32930) {
            long t32932 = i32930;
            long c329310 = t32932 / 610; t32932 %= 610;
            long c329311 = t32932 / 61; t32932 %= 61;
            long c329312 = t32932;
            r1208[i32930] = sub32(r1199[c329311 * 61 + c329312 * 1], r1207[c329311 * 1]);
        }
        /* max [max] -> r1209 */
        for (long i32933 = 0; i32933 < 610; ++i32933) {
            r1209[i32933] = max32(r1208[i32933], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1210 */
        for (long i32934 = 0; i32934 < 10; ++i32934) {
            r1210[i32934] = 0;
        }
        for (long i32935 = 0; i32935 < 610; ++i32935) {
            long t32937 = i32935;
            long c329360 = t32937 / 610; t32937 %= 610;
            long c329361 = t32937 / 61; t32937 %= 61;
            long c329362 = t32937;
            r1210[c329360 * 10 + c329361 * 1] = add32(r1210[c329360 * 10 + c329361 * 1], r1209[i32935]);
        }
        /* gt [gt] -> r1211 */
        for (long i32938 = 0; i32938 < 10; ++i32938) {
            r1211[i32938] = r1210[i32938] > r1200[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1212 */
        for (long i32939 = 0; i32939 < 10; ++i32939) {
            r1212[i32939] = r1211[i32939] == 0 ? r1202[i32939] : (r1206[i32939]);
        }
        /* select_n [select_n] -> r1213 */
        for (long i32940 = 0; i32940 < 10; ++i32940) {
            r1213[i32940] = r1211[i32940] == 0 ? r1206[i32940] : (r1203[i32940]);
        }
        memcpy(r1201, r1204, sizeof(int32_t) * 1);
        memcpy(r1202, r1212, sizeof(int32_t) * 10);
        memcpy(r1203, r1213, sizeof(int32_t) * 10);
    }
    memcpy(r1214, r1201, sizeof(int32_t) * 1);
    memcpy(r1215, r1202, sizeof(int32_t) * 10);
    memcpy(r1216, r1203, sizeof(int32_t) * 10);
    /* broadcast [broadcast_in_dim] -> r1217 */
    for (long i32941 = 0; i32941 < 300; ++i32941) {
        long t32943 = i32941;
        long c329420 = t32943 / 300; t32943 %= 300;
        long c329421 = t32943 / 10; t32943 %= 10;
        long c329422 = t32943;
        r1217[i32941] = r7[c329421 * 10 + c329422 * 1];
    }
    /* add [add] -> r1218 */
    for (long i32944 = 0; i32944 < 300; ++i32944) {
        long t32946 = i32944;
        long c329450 = t32946 / 300; t32946 %= 300;
        long c329451 = t32946 / 10; t32946 %= 10;
        long c329452 = t32946;
        r1218[i32944] = add32(r1217[c329451 * 10 + c329452 * 1], r1177[c329451 * 1]);
    }
    /* convert [convert_element_type] -> r1219 */
    for (long i32947 = 0; i32947 < 1; ++i32947) {
        r1219[i32947] = (int32_t)r42[0];
    }
    /* max [max] -> r1220 */
    for (long i32948 = 0; i32948 < 300; ++i32948) {
        r1220[i32948] = max32(r1219[0], r1218[i32948]);
    }
    /* convert [convert_element_type] -> r1221 */
    for (long i32949 = 0; i32949 < 1; ++i32949) {
        r1221[i32949] = (int32_t)r43[0];
    }
    /* min [min] -> r1222 */
    for (long i32950 = 0; i32950 < 300; ++i32950) {
        r1222[i32950] = min32(r1221[0], r1220[i32950]);
    }
    /* broadcast [broadcast_in_dim] -> r1223 */
    for (long i32951 = 0; i32951 < 300; ++i32951) {
        long t32953 = i32951;
        long c329520 = t32953 / 300; t32953 %= 300;
        long c329521 = t32953 / 10; t32953 %= 10;
        long c329522 = t32953;
        r1223[i32951] = r6[c329521 * 10 + c329522 * 1];
    }
    /* add [add] -> r1224 */
    for (long i32954 = 0; i32954 < 300; ++i32954) {
        long t32956 = i32954;
        long c329550 = t32956 / 300; t32956 %= 300;
        long c329551 = t32956 / 10; t32956 %= 10;
        long c329552 = t32956;
        r1224[i32954] = add32(r1223[c329551 * 10 + c329552 * 1], r1179[c329551 * 1]);
    }
    /* convert [convert_element_type] -> r1225 */
    for (long i32957 = 0; i32957 < 1; ++i32957) {
        r1225[i32957] = (int32_t)r42[0];
    }
    /* max [max] -> r1226 */
    for (long i32958 = 0; i32958 < 300; ++i32958) {
        r1226[i32958] = max32(r1225[0], r1224[i32958]);
    }
    /* convert [convert_element_type] -> r1227 */
    for (long i32959 = 0; i32959 < 1; ++i32959) {
        r1227[i32959] = (int32_t)r43[0];
    }
    /* min [min] -> r1228 */
    for (long i32960 = 0; i32960 < 300; ++i32960) {
        r1228[i32960] = min32(r1227[0], r1226[i32960]);
    }
    /* concat [concatenate] -> r1229 */
    for (long i32961 = 0; i32961 < 300; ++i32961) {
        long t32963 = i32961;
        long c329620 = t32963 / 300; t32963 %= 300;
        long c329621 = t32963 / 10; t32963 %= 10;
        long c329622 = t32963;
        r1229[c329620 * 600 + (c329621 + 0) * 10 + c329622 * 1] = r1222[i32961];
    }
    for (long i32964 = 0; i32964 < 300; ++i32964) {
        long t32966 = i32964;
        long c329650 = t32966 / 300; t32966 %= 300;
        long c329651 = t32966 / 10; t32966 %= 10;
        long c329652 = t32966;
        r1229[c329650 * 600 + (c329651 + 30) * 10 + c329652 * 1] = r1228[i32964];
    }
    /* broadcast [broadcast_in_dim] -> r1230 */
    for (long i32967 = 0; i32967 < 10; ++i32967) {
        long t32969 = i32967;
        long c329680 = t32969 / 10; t32969 %= 10;
        long c329681 = t32969 / 10; t32969 %= 10;
        long c329682 = t32969;
        r1230[i32967] = r8[c329682 * 1];
    }
    /* concat [concatenate] -> r1231 */
    for (long i32970 = 0; i32970 < 600; ++i32970) {
        long t32972 = i32970;
        long c329710 = t32972 / 600; t32972 %= 600;
        long c329711 = t32972 / 10; t32972 %= 10;
        long c329712 = t32972;
        r1231[c329710 * 610 + (c329711 + 0) * 10 + c329712 * 1] = r1229[i32970];
    }
    for (long i32973 = 0; i32973 < 10; ++i32973) {
        long t32975 = i32973;
        long c329740 = t32975 / 10; t32975 %= 10;
        long c329741 = t32975 / 10; t32975 %= 10;
        long c329742 = t32975;
        r1231[c329740 * 610 + (c329741 + 60) * 10 + c329742 * 1] = r1230[i32973];
    }
    /* transpose [transpose] -> r1232 */
    for (long i32976 = 0; i32976 < 610; ++i32976) {
        long t32978 = i32976;
        long c329770 = t32978 / 610; t32978 %= 610;
        long c329771 = t32978 / 61; t32978 %= 61;
        long c329772 = t32978;
        r1232[i32976] = r1231[c329770 * 610 + c329771 * 1 + c329772 * 10];
    }
    /* reduce_max [reduce_max] -> r1233 */
    for (long i32979 = 0; i32979 < 10; ++i32979) {
        r1233[i32979] = (-2147483647 - 1);
    }
    for (long i32980 = 0; i32980 < 610; ++i32980) {
        long t32982 = i32980;
        long c329810 = t32982 / 610; t32982 %= 610;
        long c329811 = t32982 / 61; t32982 %= 61;
        long c329812 = t32982;
        r1233[c329810 * 10 + c329811 * 1] = max32(r1233[c329810 * 10 + c329811 * 1], r1232[i32980]);
    }
    /* sub [sub] -> r1234 */
    for (long i32983 = 0; i32983 < 10; ++i32983) {
        r1234[i32983] = sub32(r1233[i32983], r1197[0]);
    }
    /* loop [scan] -> r1250 */
    memcpy(r1235, r1232, sizeof(int32_t) * 610);
    memcpy(r1236, r1197, sizeof(int32_t) * 1);
    memcpy(r1237, r13, sizeof(int32_t) * 1);
    memcpy(r1238, r1234, sizeof(int32_t) * 10);
    memcpy(r1239, r1233, sizeof(int32_t) * 10);
    for (long t32984 = 0; t32984 < 11; ++t32984) {
        /* add [add] -> r1240 */
        for (long i33985 = 0; i33985 < 1; ++i33985) {
            r1240[i33985] = add32(r1237[0], r9[0]);
        }
        /* add [add] -> r1241 */
        for (long i33986 = 0; i33986 < 10; ++i33986) {
            r1241[i33986] = add32(r1238[i33986], r1239[i33986]);
        }
        /* shra [shift_right_arithmetic] -> r1242 */
        for (long i33987 = 0; i33987 < 10; ++i33987) {
            r1242[i33987] = asr32(r1241[i33987], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1243 */
        for (long i33988 = 0; i33988 < 10; ++i33988) {
            long t33990 = i33988;
            long c339890 = t33990 / 10; t33990 %= 10;
            long c339891 = t33990 / 1; t33990 %= 1;
            long c339892 = t33990;
            r1243[i33988] = r1242[c339891 * 1];
        }
        /* sub [sub] -> r1244 */
        for (long i33991 = 0; i33991 < 610; ++i33991) {
            long t33993 = i33991;
            long c339920 = t33993 / 610; t33993 %= 610;
            long c339921 = t33993 / 61; t33993 %= 61;
            long c339922 = t33993;
            r1244[i33991] = sub32(r1235[c339921 * 61 + c339922 * 1], r1243[c339921 * 1]);
        }
        /* max [max] -> r1245 */
        for (long i33994 = 0; i33994 < 610; ++i33994) {
            r1245[i33994] = max32(r1244[i33994], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1246 */
        for (long i33995 = 0; i33995 < 10; ++i33995) {
            r1246[i33995] = 0;
        }
        for (long i33996 = 0; i33996 < 610; ++i33996) {
            long t33998 = i33996;
            long c339970 = t33998 / 610; t33998 %= 610;
            long c339971 = t33998 / 61; t33998 %= 61;
            long c339972 = t33998;
            r1246[c339970 * 10 + c339971 * 1] = add32(r1246[c339970 * 10 + c339971 * 1], r1245[i33996]);
        }
        /* gt [gt] -> r1247 */
        for (long i33999 = 0; i33999 < 10; ++i33999) {
            r1247[i33999] = r1246[i33999] > r1236[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1248 */
        for (long i34000 = 0; i34000 < 10; ++i34000) {
            r1248[i34000] = r1247[i34000] == 0 ? r1238[i34000] : (r1242[i34000]);
        }
        /* select_n [select_n] -> r1249 */
        for (long i34001 = 0; i34001 < 10; ++i34001) {
            r1249[i34001] = r1247[i34001] == 0 ? r1242[i34001] : (r1239[i34001]);
        }
        memcpy(r1237, r1240, sizeof(int32_t) * 1);
        memcpy(r1238, r1248, sizeof(int32_t) * 10);
        memcpy(r1239, r1249, sizeof(int32_t) * 10);
    }
    memcpy(r1250, r1237, sizeof(int32_t) * 1);
    memcpy(r1251, r1238, sizeof(int32_t) * 10);
    memcpy(r1252, r1239, sizeof(int32_t) * 10);
    /* broadcast [broadcast_in_dim] -> r1253 */
    for (long i34002 = 0; i34002 < 10; ++i34002) {
        long t34004 = i34002;
        long c340030 = t34004 / 10; t34004 %= 10;
        long c340031 = t34004 / 1; t34004 %= 1;
        long c340032 = t34004;
        r1253[i34002] = r1216[c340031 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r1254 */
    for (long i34005 = 0; i34005 < 10; ++i34005) {
        long t34007 = i34005;
        long c340060 = t34007 / 10; t34007 %= 10;
        long c340061 = t34007 / 1; t34007 %= 1;
        long c340062 = t34007;
        r1254[i34005] = r1252[c340061 * 1];
    }
    /* concat [concatenate] -> r1255 */
    for (long i34008 = 0; i34008 < 10; ++i34008) {
        long t34010 = i34008;
        long c340090 = t34010 / 10; t34010 %= 10;
        long c340091 = t34010 / 1; t34010 %= 1;
        long c340092 = t34010;
        r1255[c340090 * 20 + c340091 * 2 + (c340092 + 0) * 1] = r1253[i34008];
    }
    for (long i34011 = 0; i34011 < 10; ++i34011) {
        long t34013 = i34011;
        long c340120 = t34013 / 10; t34013 %= 10;
        long c340121 = t34013 / 1; t34013 %= 1;
        long c340122 = t34013;
        r1255[c340120 * 20 + c340121 * 2 + (c340122 + 1) * 1] = r1254[i34011];
    }
    /* reduce_max [reduce_max] -> r1256 */
    for (long i34014 = 0; i34014 < 10; ++i34014) {
        r1256[i34014] = (-2147483647 - 1);
    }
    for (long i34015 = 0; i34015 < 20; ++i34015) {
        long t34017 = i34015;
        long c340160 = t34017 / 20; t34017 %= 20;
        long c340161 = t34017 / 2; t34017 %= 2;
        long c340162 = t34017;
        r1256[c340160 * 10 + c340161 * 1] = max32(r1256[c340160 * 10 + c340161 * 1], r1255[i34015]);
    }
    /* sub [sub] -> r1258 */
    for (long i34018 = 0; i34018 < 10; ++i34018) {
        r1258[i34018] = sub32(r1256[i34018], r1257[0]);
    }
    /* loop [scan] -> r1274 */
    memcpy(r1259, r1255, sizeof(int32_t) * 20);
    memcpy(r1260, r1257, sizeof(int32_t) * 1);
    memcpy(r1261, r13, sizeof(int32_t) * 1);
    memcpy(r1262, r1258, sizeof(int32_t) * 10);
    memcpy(r1263, r1256, sizeof(int32_t) * 10);
    for (long t34019 = 0; t34019 < 8; ++t34019) {
        /* add [add] -> r1264 */
        for (long i35020 = 0; i35020 < 1; ++i35020) {
            r1264[i35020] = add32(r1261[0], r9[0]);
        }
        /* add [add] -> r1265 */
        for (long i35021 = 0; i35021 < 10; ++i35021) {
            r1265[i35021] = add32(r1262[i35021], r1263[i35021]);
        }
        /* shra [shift_right_arithmetic] -> r1266 */
        for (long i35022 = 0; i35022 < 10; ++i35022) {
            r1266[i35022] = asr32(r1265[i35022], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1267 */
        for (long i35023 = 0; i35023 < 10; ++i35023) {
            long t35025 = i35023;
            long c350240 = t35025 / 10; t35025 %= 10;
            long c350241 = t35025 / 1; t35025 %= 1;
            long c350242 = t35025;
            r1267[i35023] = r1266[c350241 * 1];
        }
        /* sub [sub] -> r1268 */
        for (long i35026 = 0; i35026 < 20; ++i35026) {
            long t35028 = i35026;
            long c350270 = t35028 / 20; t35028 %= 20;
            long c350271 = t35028 / 2; t35028 %= 2;
            long c350272 = t35028;
            r1268[i35026] = sub32(r1259[c350271 * 2 + c350272 * 1], r1267[c350271 * 1]);
        }
        /* max [max] -> r1269 */
        for (long i35029 = 0; i35029 < 20; ++i35029) {
            r1269[i35029] = max32(r1268[i35029], r13[0]);
        }
        /* reduce_sum [reduce_sum] -> r1270 */
        for (long i35030 = 0; i35030 < 10; ++i35030) {
            r1270[i35030] = 0;
        }
        for (long i35031 = 0; i35031 < 20; ++i35031) {
            long t35033 = i35031;
            long c350320 = t35033 / 20; t35033 %= 20;
            long c350321 = t35033 / 2; t35033 %= 2;
            long c350322 = t35033;
            r1270[c350320 * 10 + c350321 * 1] = add32(r1270[c350320 * 10 + c350321 * 1], r1269[i35031]);
        }
        /* gt [gt] -> r1271 */
        for (long i35034 = 0; i35034 < 10; ++i35034) {
            r1271[i35034] = r1270[i35034] > r1260[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1272 */
        for (long i35035 = 0; i35035 < 10; ++i35035) {
            r1272[i35035] = r1271[i35035] == 0 ? r1262[i35035] : (r1266[i35035]);
        }
        /* select_n [select_n] -> r1273 */
        for (long i35036 = 0; i35036 < 10; ++i35036) {
            r1273[i35036] = r1271[i35036] == 0 ? r1266[i35036] : (r1263[i35036]);
        }
        memcpy(r1261, r1264, sizeof(int32_t) * 1);
        memcpy(r1262, r1272, sizeof(int32_t) * 10);
        memcpy(r1263, r1273, sizeof(int32_t) * 10);
    }
    memcpy(r1274, r1261, sizeof(int32_t) * 1);
    memcpy(r1275, r1262, sizeof(int32_t) * 10);
    memcpy(r1276, r1263, sizeof(int32_t) * 10);
    /* sub [sub] -> r1277 */
    for (long i35037 = 0; i35037 < 10; ++i35037) {
        r1277[i35037] = sub32(r1216[i35037], r1276[i35037]);
    }
    /* max [max] -> r1278 */
    for (long i35038 = 0; i35038 < 10; ++i35038) {
        r1278[i35038] = max32(r1277[i35038], r13[0]);
    }
    /* sub [sub] -> r1279 */
    for (long i35039 = 0; i35039 < 10; ++i35039) {
        r1279[i35039] = sub32(r1252[i35039], r1276[i35039]);
    }
    /* max [max] -> r1280 */
    for (long i35040 = 0; i35040 < 10; ++i35040) {
        r1280[i35040] = max32(r1279[i35040], r13[0]);
    }
    /* sub [sub] -> r1281 */
    for (long i35041 = 0; i35041 < 10; ++i35041) {
        r1281[i35041] = sub32(r1278[i35041], r1280[i35041]);
    }
}

int main(int argc, char **argv) {
    if (argc != 3) { fprintf(stderr, "usage: %s in.bin out.bin\n", argv[0]); return 2; }
    FILE *fi = fopen(argv[1], "rb");
    if (!fi) { perror("in"); return 2; }
    if (fread(r0, sizeof(int32_t), 16000, fi) != 16000) { fprintf(stderr, "short read\n"); return 2; }
    fclose(fi);
    program_run();
    FILE *fo = fopen(argv[2], "wb");
    if (!fo) { perror("out"); return 2; }
    fwrite(r1281, sizeof(int32_t), 10, fo);
    fwrite(r1175, sizeof(int32_t), 30, fo);
    fwrite(r1141, sizeof(int32_t), 30, fo);
    fclose(fo);
    return 0;
}
