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
    175
};
static const int32_t rom11_lit[1] = {
    -512
};
static const int32_t rom12_lit[1] = {
    511
};
static const int32_t rom13_lit[1] = {
    512
};
static const int32_t rom14_lit[1] = {
    166
};
static const int32_t rom15_lit[1] = {
    -128
};
static const int32_t rom16_lit[1] = {
    127
};
static const int32_t rom17_lit[1] = {
    95
};
static const int32_t rom18_lit[1] = {
    86
};
static const int32_t rom19_lit[1] = {
    55
};
static const int32_t rom20_lit[1] = {
    2
};
static const int32_t rom21_lit[1] = {
    46
};
static const int32_t rom22_lit[1] = {
    35
};
static const int32_t rom23_lit[1] = {
    3
};
static const int32_t rom24_lit[1] = {
    26
};
static const int32_t rom25_lit[1] = {
    25
};
static const int32_t rom26_lit[1] = {
    4
};
static const int32_t rom27_lit[1] = {
    16
};
static const int32_t rom28_lit[1] = {
    20
};
static const int32_t rom29_lit[1] = {
    5
};
static const int32_t rom30_lit[1] = {
    256
};
static const int32_t rom31_lit[1] = {
    32
};

static int32_t r0[15];
static int32_t r1[15];
static int32_t r2[15];
static int32_t r3[15];
static int32_t r4[15];
static int32_t r5[15];
static int32_t r6[1];
static int32_t r7[1];
static int32_t r8[1];
static int32_t r9[1];
static int32_t r10[1];
static int32_t r11[1];
static int32_t r12[30];
static int32_t r13[1];
static int32_t r14[1];
static uint8_t r15[1];
static int32_t r16[160];
static int32_t r17[1];
static const int32_t *const r18 = rom0_c;
static const int32_t *const r19 = rom1_c;
static const int32_t *const r20 = rom2_c;
static const int32_t *const r21 = rom3_c;
static const int32_t *const r22 = rom4_c;
static const int32_t *const r23 = rom5_c;
static const int32_t *const r24 = rom6_c;
static const int32_t *const r25 = rom7_c;
static int32_t r26[160];
static int32_t r27[1];
static int32_t r28[1];
static int32_t r29[175];
static const int32_t *const r30 = rom8_lit;
static int32_t r31[175];
static int32_t r32[80];
static int32_t r33[80];
static int32_t r34[160];
static int32_t r35[160];
static int32_t r36[16];
static int32_t r37[16];
static int32_t r38[2560];
static const int32_t *const r39 = rom9_lit;
static uint8_t r40[2560];
static const int32_t *const r41 = rom10_lit;
static int32_t r42[2560];
static int32_t r43[2560];
static int32_t r44[2560];
static int32_t r45[2560];
static int32_t r46[2560];
static int32_t r47[12800];
static const int32_t *const r48 = rom11_lit;
static const int32_t *const r49 = rom12_lit;
static int32_t r50[1];
static int32_t r51[12800];
static int32_t r52[1];
static int32_t r53[12800];
static int32_t r54[12800];
static int32_t r55[1];
static int32_t r56[12800];
static int32_t r57[1];
static int32_t r58[12800];
static int32_t r59[12800];
static int32_t r60[800];
static const int32_t *const r61 = rom13_lit;
static int32_t r62[800];
static int32_t r63[12800];
static int32_t r64[1];
static int32_t r65[1];
static int32_t r66[800];
static int32_t r67[800];
static int32_t r68[1];
static int32_t r69[800];
static int32_t r70[800];
static int32_t r71[800];
static int32_t r72[12800];
static int32_t r73[12800];
static int32_t r74[800];
static int32_t r75[12800];
static int32_t r76[800];
static int32_t r77[12800];
static int32_t r78[12800];
static int32_t r79[800];
static int32_t r80[800];
static uint8_t r81[800];
static int32_t r82[800];
static int32_t r83[800];
static int32_t r84[1];
static int32_t r85[800];
static int32_t r86[800];
static int32_t r87[12800];
static int32_t r88[800];
static int32_t r89[800];
static int32_t r90[12800];
static int32_t r91[1];
static int32_t r92[1];
static int32_t r93[800];
static int32_t r94[800];
static int32_t r95[1];
static int32_t r96[800];
static int32_t r97[800];
static int32_t r98[800];
static int32_t r99[12800];
static int32_t r100[12800];
static int32_t r101[800];
static int32_t r102[12800];
static int32_t r103[800];
static int32_t r104[12800];
static int32_t r105[12800];
static int32_t r106[800];
static int32_t r107[800];
static uint8_t r108[800];
static int32_t r109[800];
static int32_t r110[800];
static int32_t r111[1];
static int32_t r112[800];
static int32_t r113[800];
static int32_t r114[800];
static int32_t r115[800];
static int32_t r116[1];
static int32_t r117[800];
static int32_t r118[800];
static int32_t r119[1];
static uint8_t r120[800];
static int32_t r121[1];
static int32_t r122[800];
static int32_t r123[800];
static int32_t r124[5];
static int32_t r125[5];
static uint8_t r126[1];
static int32_t r127[1];
static int32_t r128[1];
static int32_t r129[1];
static int32_t r130[15];
static int32_t r131[1];
static int32_t r132[1];
static int32_t r133[165];
static int32_t r134[165];
static int32_t r135[1];
static int32_t r136[166];
static int32_t r137[80];
static int32_t r138[80];
static int32_t r139[80];
static int32_t r140[6];
static int32_t r141[6];
static int32_t r142[480];
static int32_t r143[480];
static int32_t r144[1];
static int32_t r145[480];
static uint8_t r146[480];
static const int32_t *const r147 = rom14_lit;
static int32_t r148[480];
static int32_t r149[480];
static int32_t r150[480];
static int32_t r151[480];
static int32_t r152[6];
static int32_t r153[480];
static int32_t r154[1];
static int32_t r155[480];
static int32_t r156[1];
static int32_t r157[480];
static int32_t r158[6];
static int32_t r159[480];
static int32_t r160[1];
static int32_t r161[480];
static int32_t r162[1];
static int32_t r163[480];
static int32_t r164[480];
static int32_t r165[80];
static int32_t r166[80];
static int32_t r167[480];
static int32_t r168[1];
static int32_t r169[1];
static int32_t r170[80];
static int32_t r171[80];
static int32_t r172[1];
static int32_t r173[80];
static int32_t r174[80];
static int32_t r175[80];
static int32_t r176[480];
static int32_t r177[480];
static int32_t r178[80];
static int32_t r179[480];
static int32_t r180[80];
static int32_t r181[480];
static int32_t r182[480];
static int32_t r183[80];
static int32_t r184[80];
static uint8_t r185[80];
static int32_t r186[80];
static int32_t r187[80];
static int32_t r188[1];
static int32_t r189[80];
static int32_t r190[80];
static int32_t r191[480];
static int32_t r192[80];
static int32_t r193[80];
static int32_t r194[480];
static int32_t r195[1];
static int32_t r196[1];
static int32_t r197[80];
static int32_t r198[80];
static int32_t r199[1];
static int32_t r200[80];
static int32_t r201[80];
static int32_t r202[80];
static int32_t r203[480];
static int32_t r204[480];
static int32_t r205[80];
static int32_t r206[480];
static int32_t r207[80];
static int32_t r208[480];
static int32_t r209[480];
static int32_t r210[80];
static int32_t r211[80];
static uint8_t r212[80];
static int32_t r213[80];
static int32_t r214[80];
static int32_t r215[1];
static int32_t r216[80];
static int32_t r217[80];
static int32_t r218[80];
static int32_t r219[80];
static const int32_t *const r220 = rom15_lit;
static const int32_t *const r221 = rom16_lit;
static int32_t r222[1];
static int32_t r223[80];
static int32_t r224[1];
static int32_t r225[80];
static int32_t r226[1];
static int32_t r227[1];
static int32_t r228[1];
static int32_t r229[1];
static int32_t r230[95];
static int32_t r231[95];
static int32_t r232[80];
static int32_t r233[80];
static int32_t r234[80];
static int32_t r235[80];
static int32_t r236[16];
static int32_t r237[16];
static int32_t r238[1280];
static uint8_t r239[1280];
static const int32_t *const r240 = rom17_lit;
static int32_t r241[1280];
static int32_t r242[1280];
static int32_t r243[1280];
static int32_t r244[1280];
static int32_t r245[1280];
static int32_t r246[6400];
static int32_t r247[1];
static int32_t r248[6400];
static int32_t r249[1];
static int32_t r250[6400];
static int32_t r251[6400];
static int32_t r252[1];
static int32_t r253[6400];
static int32_t r254[1];
static int32_t r255[6400];
static int32_t r256[6400];
static int32_t r257[400];
static int32_t r258[400];
static int32_t r259[6400];
static int32_t r260[1];
static int32_t r261[1];
static int32_t r262[400];
static int32_t r263[400];
static int32_t r264[1];
static int32_t r265[400];
static int32_t r266[400];
static int32_t r267[400];
static int32_t r268[6400];
static int32_t r269[6400];
static int32_t r270[400];
static int32_t r271[6400];
static int32_t r272[400];
static int32_t r273[6400];
static int32_t r274[6400];
static int32_t r275[400];
static int32_t r276[400];
static uint8_t r277[400];
static int32_t r278[400];
static int32_t r279[400];
static int32_t r280[1];
static int32_t r281[400];
static int32_t r282[400];
static int32_t r283[6400];
static int32_t r284[400];
static int32_t r285[400];
static int32_t r286[6400];
static int32_t r287[1];
static int32_t r288[1];
static int32_t r289[400];
static int32_t r290[400];
static int32_t r291[1];
static int32_t r292[400];
static int32_t r293[400];
static int32_t r294[400];
static int32_t r295[6400];
static int32_t r296[6400];
static int32_t r297[400];
static int32_t r298[6400];
static int32_t r299[400];
static int32_t r300[6400];
static int32_t r301[6400];
static int32_t r302[400];
static int32_t r303[400];
static uint8_t r304[400];
static int32_t r305[400];
static int32_t r306[400];
static int32_t r307[1];
static int32_t r308[400];
static int32_t r309[400];
static int32_t r310[400];
static int32_t r311[400];
static int32_t r312[1];
static int32_t r313[400];
static int32_t r314[400];
static int32_t r315[1];
static uint8_t r316[400];
static int32_t r317[1];
static int32_t r318[400];
static int32_t r319[400];
static int32_t r320[5];
static int32_t r321[5];
static uint8_t r322[1];
static int32_t r323[1];
static int32_t r324[1];
static int32_t r325[1];
static int32_t r326[15];
static int32_t r327[1];
static int32_t r328[1];
static int32_t r329[85];
static int32_t r330[85];
static int32_t r331[1];
static int32_t r332[86];
static int32_t r333[40];
static int32_t r334[40];
static int32_t r335[40];
static int32_t r336[6];
static int32_t r337[6];
static int32_t r338[240];
static int32_t r339[240];
static int32_t r340[1];
static int32_t r341[240];
static uint8_t r342[240];
static const int32_t *const r343 = rom18_lit;
static int32_t r344[240];
static int32_t r345[240];
static int32_t r346[240];
static int32_t r347[240];
static int32_t r348[6];
static int32_t r349[240];
static int32_t r350[1];
static int32_t r351[240];
static int32_t r352[1];
static int32_t r353[240];
static int32_t r354[6];
static int32_t r355[240];
static int32_t r356[1];
static int32_t r357[240];
static int32_t r358[1];
static int32_t r359[240];
static int32_t r360[240];
static int32_t r361[40];
static int32_t r362[40];
static int32_t r363[240];
static int32_t r364[1];
static int32_t r365[1];
static int32_t r366[40];
static int32_t r367[40];
static int32_t r368[1];
static int32_t r369[40];
static int32_t r370[40];
static int32_t r371[40];
static int32_t r372[240];
static int32_t r373[240];
static int32_t r374[40];
static int32_t r375[240];
static int32_t r376[40];
static int32_t r377[240];
static int32_t r378[240];
static int32_t r379[40];
static int32_t r380[40];
static uint8_t r381[40];
static int32_t r382[40];
static int32_t r383[40];
static int32_t r384[1];
static int32_t r385[40];
static int32_t r386[40];
static int32_t r387[240];
static int32_t r388[40];
static int32_t r389[40];
static int32_t r390[240];
static int32_t r391[1];
static int32_t r392[1];
static int32_t r393[40];
static int32_t r394[40];
static int32_t r395[1];
static int32_t r396[40];
static int32_t r397[40];
static int32_t r398[40];
static int32_t r399[240];
static int32_t r400[240];
static int32_t r401[40];
static int32_t r402[240];
static int32_t r403[40];
static int32_t r404[240];
static int32_t r405[240];
static int32_t r406[40];
static int32_t r407[40];
static uint8_t r408[40];
static int32_t r409[40];
static int32_t r410[40];
static int32_t r411[1];
static int32_t r412[40];
static int32_t r413[40];
static int32_t r414[40];
static int32_t r415[40];
static int32_t r416[1];
static int32_t r417[40];
static int32_t r418[1];
static int32_t r419[40];
static int32_t r420[1];
static int32_t r421[1];
static int32_t r422[1];
static int32_t r423[1];
static int32_t r424[55];
static int32_t r425[55];
static int32_t r426[80];
static int32_t r427[80];
static int32_t r428[40];
static int32_t r429[40];
static int32_t r430[16];
static int32_t r431[16];
static int32_t r432[640];
static uint8_t r433[640];
static const int32_t *const r434 = rom19_lit;
static int32_t r435[640];
static int32_t r436[640];
static int32_t r437[640];
static int32_t r438[640];
static int32_t r439[640];
static int32_t r440[3200];
static int32_t r441[1];
static int32_t r442[3200];
static int32_t r443[1];
static int32_t r444[3200];
static int32_t r445[3200];
static int32_t r446[1];
static int32_t r447[3200];
static int32_t r448[1];
static int32_t r449[3200];
static int32_t r450[3200];
static int32_t r451[200];
static int32_t r452[200];
static int32_t r453[3200];
static int32_t r454[1];
static int32_t r455[1];
static int32_t r456[200];
static int32_t r457[200];
static int32_t r458[1];
static int32_t r459[200];
static int32_t r460[200];
static int32_t r461[200];
static int32_t r462[3200];
static int32_t r463[3200];
static int32_t r464[200];
static int32_t r465[3200];
static int32_t r466[200];
static int32_t r467[3200];
static int32_t r468[3200];
static int32_t r469[200];
static int32_t r470[200];
static uint8_t r471[200];
static int32_t r472[200];
static int32_t r473[200];
static int32_t r474[1];
static int32_t r475[200];
static int32_t r476[200];
static int32_t r477[3200];
static int32_t r478[200];
static int32_t r479[200];
static int32_t r480[3200];
static int32_t r481[1];
static int32_t r482[1];
static int32_t r483[200];
static int32_t r484[200];
static int32_t r485[1];
static int32_t r486[200];
static int32_t r487[200];
static int32_t r488[200];
static int32_t r489[3200];
static int32_t r490[3200];
static int32_t r491[200];
static int32_t r492[3200];
static int32_t r493[200];
static int32_t r494[3200];
static int32_t r495[3200];
static int32_t r496[200];
static int32_t r497[200];
static uint8_t r498[200];
static int32_t r499[200];
static int32_t r500[200];
static int32_t r501[1];
static int32_t r502[200];
static int32_t r503[200];
static int32_t r504[200];
static int32_t r505[200];
static int32_t r506[1];
static int32_t r507[200];
static int32_t r508[200];
static int32_t r509[1];
static uint8_t r510[200];
static int32_t r511[1];
static int32_t r512[200];
static int32_t r513[200];
static int32_t r514[5];
static const int32_t *const r515 = rom20_lit;
static int32_t r516[5];
static uint8_t r517[1];
static int32_t r518[1];
static int32_t r519[1];
static int32_t r520[1];
static int32_t r521[15];
static int32_t r522[1];
static int32_t r523[1];
static int32_t r524[45];
static int32_t r525[45];
static int32_t r526[1];
static int32_t r527[46];
static int32_t r528[20];
static int32_t r529[20];
static int32_t r530[20];
static int32_t r531[6];
static int32_t r532[6];
static int32_t r533[120];
static int32_t r534[120];
static int32_t r535[1];
static int32_t r536[120];
static uint8_t r537[120];
static const int32_t *const r538 = rom21_lit;
static int32_t r539[120];
static int32_t r540[120];
static int32_t r541[120];
static int32_t r542[120];
static int32_t r543[6];
static int32_t r544[120];
static int32_t r545[1];
static int32_t r546[120];
static int32_t r547[1];
static int32_t r548[120];
static int32_t r549[6];
static int32_t r550[120];
static int32_t r551[1];
static int32_t r552[120];
static int32_t r553[1];
static int32_t r554[120];
static int32_t r555[120];
static int32_t r556[20];
static int32_t r557[20];
static int32_t r558[120];
static int32_t r559[1];
static int32_t r560[1];
static int32_t r561[20];
static int32_t r562[20];
static int32_t r563[1];
static int32_t r564[20];
static int32_t r565[20];
static int32_t r566[20];
static int32_t r567[120];
static int32_t r568[120];
static int32_t r569[20];
static int32_t r570[120];
static int32_t r571[20];
static int32_t r572[120];
static int32_t r573[120];
static int32_t r574[20];
static int32_t r575[20];
static uint8_t r576[20];
static int32_t r577[20];
static int32_t r578[20];
static int32_t r579[1];
static int32_t r580[20];
static int32_t r581[20];
static int32_t r582[120];
static int32_t r583[20];
static int32_t r584[20];
static int32_t r585[120];
static int32_t r586[1];
static int32_t r587[1];
static int32_t r588[20];
static int32_t r589[20];
static int32_t r590[1];
static int32_t r591[20];
static int32_t r592[20];
static int32_t r593[20];
static int32_t r594[120];
static int32_t r595[120];
static int32_t r596[20];
static int32_t r597[120];
static int32_t r598[20];
static int32_t r599[120];
static int32_t r600[120];
static int32_t r601[20];
static int32_t r602[20];
static uint8_t r603[20];
static int32_t r604[20];
static int32_t r605[20];
static int32_t r606[1];
static int32_t r607[20];
static int32_t r608[20];
static int32_t r609[20];
static int32_t r610[20];
static int32_t r611[1];
static int32_t r612[20];
static int32_t r613[1];
static int32_t r614[20];
static int32_t r615[1];
static int32_t r616[1];
static int32_t r617[1];
static int32_t r618[1];
static int32_t r619[35];
static int32_t r620[35];
static int32_t r621[80];
static int32_t r622[80];
static int32_t r623[20];
static int32_t r624[20];
static int32_t r625[16];
static int32_t r626[16];
static int32_t r627[320];
static uint8_t r628[320];
static const int32_t *const r629 = rom22_lit;
static int32_t r630[320];
static int32_t r631[320];
static int32_t r632[320];
static int32_t r633[320];
static int32_t r634[320];
static int32_t r635[1600];
static int32_t r636[1];
static int32_t r637[1600];
static int32_t r638[1];
static int32_t r639[1600];
static int32_t r640[1600];
static int32_t r641[1];
static int32_t r642[1600];
static int32_t r643[1];
static int32_t r644[1600];
static int32_t r645[1600];
static int32_t r646[100];
static int32_t r647[100];
static int32_t r648[1600];
static int32_t r649[1];
static int32_t r650[1];
static int32_t r651[100];
static int32_t r652[100];
static int32_t r653[1];
static int32_t r654[100];
static int32_t r655[100];
static int32_t r656[100];
static int32_t r657[1600];
static int32_t r658[1600];
static int32_t r659[100];
static int32_t r660[1600];
static int32_t r661[100];
static int32_t r662[1600];
static int32_t r663[1600];
static int32_t r664[100];
static int32_t r665[100];
static uint8_t r666[100];
static int32_t r667[100];
static int32_t r668[100];
static int32_t r669[1];
static int32_t r670[100];
static int32_t r671[100];
static int32_t r672[1600];
static int32_t r673[100];
static int32_t r674[100];
static int32_t r675[1600];
static int32_t r676[1];
static int32_t r677[1];
static int32_t r678[100];
static int32_t r679[100];
static int32_t r680[1];
static int32_t r681[100];
static int32_t r682[100];
static int32_t r683[100];
static int32_t r684[1600];
static int32_t r685[1600];
static int32_t r686[100];
static int32_t r687[1600];
static int32_t r688[100];
static int32_t r689[1600];
static int32_t r690[1600];
static int32_t r691[100];
static int32_t r692[100];
static uint8_t r693[100];
static int32_t r694[100];
static int32_t r695[100];
static int32_t r696[1];
static int32_t r697[100];
static int32_t r698[100];
static int32_t r699[100];
static int32_t r700[100];
static int32_t r701[1];
static int32_t r702[100];
static int32_t r703[100];
static int32_t r704[1];
static uint8_t r705[100];
static int32_t r706[1];
static int32_t r707[100];
static int32_t r708[100];
static int32_t r709[5];
static const int32_t *const r710 = rom23_lit;
static int32_t r711[5];
static uint8_t r712[1];
static int32_t r713[1];
static int32_t r714[1];
static int32_t r715[1];
static int32_t r716[15];
static int32_t r717[1];
static int32_t r718[1];
static int32_t r719[25];
static int32_t r720[25];
static int32_t r721[1];
static int32_t r722[26];
static int32_t r723[10];
static int32_t r724[10];
static int32_t r725[10];
static int32_t r726[6];
static int32_t r727[6];
static int32_t r728[60];
static int32_t r729[60];
static int32_t r730[1];
static int32_t r731[60];
static uint8_t r732[60];
static const int32_t *const r733 = rom24_lit;
static int32_t r734[60];
static int32_t r735[60];
static int32_t r736[60];
static int32_t r737[60];
static int32_t r738[6];
static int32_t r739[60];
static int32_t r740[1];
static int32_t r741[60];
static int32_t r742[1];
static int32_t r743[60];
static int32_t r744[6];
static int32_t r745[60];
static int32_t r746[1];
static int32_t r747[60];
static int32_t r748[1];
static int32_t r749[60];
static int32_t r750[60];
static int32_t r751[10];
static int32_t r752[10];
static int32_t r753[60];
static int32_t r754[1];
static int32_t r755[1];
static int32_t r756[10];
static int32_t r757[10];
static int32_t r758[1];
static int32_t r759[10];
static int32_t r760[10];
static int32_t r761[10];
static int32_t r762[60];
static int32_t r763[60];
static int32_t r764[10];
static int32_t r765[60];
static int32_t r766[10];
static int32_t r767[60];
static int32_t r768[60];
static int32_t r769[10];
static int32_t r770[10];
static uint8_t r771[10];
static int32_t r772[10];
static int32_t r773[10];
static int32_t r774[1];
static int32_t r775[10];
static int32_t r776[10];
static int32_t r777[60];
static int32_t r778[10];
static int32_t r779[10];
static int32_t r780[60];
static int32_t r781[1];
static int32_t r782[1];
static int32_t r783[10];
static int32_t r784[10];
static int32_t r785[1];
static int32_t r786[10];
static int32_t r787[10];
static int32_t r788[10];
static int32_t r789[60];
static int32_t r790[60];
static int32_t r791[10];
static int32_t r792[60];
static int32_t r793[10];
static int32_t r794[60];
static int32_t r795[60];
static int32_t r796[10];
static int32_t r797[10];
static uint8_t r798[10];
static int32_t r799[10];
static int32_t r800[10];
static int32_t r801[1];
static int32_t r802[10];
static int32_t r803[10];
static int32_t r804[10];
static int32_t r805[10];
static int32_t r806[1];
static int32_t r807[10];
static int32_t r808[1];
static int32_t r809[10];
static int32_t r810[1];
static int32_t r811[1];
static int32_t r812[1];
static int32_t r813[1];
static int32_t r814[25];
static int32_t r815[25];
static int32_t r816[80];
static int32_t r817[80];
static int32_t r818[10];
static int32_t r819[10];
static int32_t r820[16];
static int32_t r821[16];
static int32_t r822[160];
static uint8_t r823[160];
static const int32_t *const r824 = rom25_lit;
static int32_t r825[160];
static int32_t r826[160];
static int32_t r827[160];
static int32_t r828[160];
static int32_t r829[160];
static int32_t r830[800];
static int32_t r831[1];
static int32_t r832[800];
static int32_t r833[1];
static int32_t r834[800];
static int32_t r835[800];
static int32_t r836[1];
static int32_t r837[800];
static int32_t r838[1];
static int32_t r839[800];
static int32_t r840[800];
static int32_t r841[50];
static int32_t r842[50];
static int32_t r843[800];
static int32_t r844[1];
static int32_t r845[1];
static int32_t r846[50];
static int32_t r847[50];
static int32_t r848[1];
static int32_t r849[50];
static int32_t r850[50];
static int32_t r851[50];
static int32_t r852[800];
static int32_t r853[800];
static int32_t r854[50];
static int32_t r855[800];
static int32_t r856[50];
static int32_t r857[800];
static int32_t r858[800];
static int32_t r859[50];
static int32_t r860[50];
static uint8_t r861[50];
static int32_t r862[50];
static int32_t r863[50];
static int32_t r864[1];
static int32_t r865[50];
static int32_t r866[50];
static int32_t r867[800];
static int32_t r868[50];
static int32_t r869[50];
static int32_t r870[800];
static int32_t r871[1];
static int32_t r872[1];
static int32_t r873[50];
static int32_t r874[50];
static int32_t r875[1];
static int32_t r876[50];
static int32_t r877[50];
static int32_t r878[50];
static int32_t r879[800];
static int32_t r880[800];
static int32_t r881[50];
static int32_t r882[800];
static int32_t r883[50];
static int32_t r884[800];
static int32_t r885[800];
static int32_t r886[50];
static int32_t r887[50];
static uint8_t r888[50];
static int32_t r889[50];
static int32_t r890[50];
static int32_t r891[1];
static int32_t r892[50];
static int32_t r893[50];
static int32_t r894[50];
static int32_t r895[50];
static int32_t r896[1];
static int32_t r897[50];
static int32_t r898[50];
static int32_t r899[1];
static uint8_t r900[50];
static int32_t r901[1];
static int32_t r902[50];
static int32_t r903[50];
static int32_t r904[5];
static const int32_t *const r905 = rom26_lit;
static int32_t r906[5];
static uint8_t r907[1];
static int32_t r908[1];
static int32_t r909[1];
static int32_t r910[1];
static int32_t r911[15];
static int32_t r912[1];
static int32_t r913[1];
static int32_t r914[15];
static int32_t r915[15];
static int32_t r916[1];
static int32_t r917[16];
static int32_t r918[5];
static int32_t r919[5];
static int32_t r920[5];
static int32_t r921[6];
static int32_t r922[6];
static int32_t r923[30];
static int32_t r924[30];
static int32_t r925[1];
static int32_t r926[30];
static uint8_t r927[30];
static const int32_t *const r928 = rom27_lit;
static int32_t r929[30];
static int32_t r930[30];
static int32_t r931[30];
static int32_t r932[30];
static int32_t r933[6];
static int32_t r934[30];
static int32_t r935[1];
static int32_t r936[30];
static int32_t r937[1];
static int32_t r938[30];
static int32_t r939[6];
static int32_t r940[30];
static int32_t r941[1];
static int32_t r942[30];
static int32_t r943[1];
static int32_t r944[30];
static int32_t r945[30];
static int32_t r946[5];
static int32_t r947[5];
static int32_t r948[30];
static int32_t r949[1];
static int32_t r950[1];
static int32_t r951[5];
static int32_t r952[5];
static int32_t r953[1];
static int32_t r954[5];
static int32_t r955[5];
static int32_t r956[5];
static int32_t r957[30];
static int32_t r958[30];
static int32_t r959[5];
static int32_t r960[30];
static int32_t r961[5];
static int32_t r962[30];
static int32_t r963[30];
static int32_t r964[5];
static int32_t r965[5];
static uint8_t r966[5];
static int32_t r967[5];
static int32_t r968[5];
static int32_t r969[1];
static int32_t r970[5];
static int32_t r971[5];
static int32_t r972[30];
static int32_t r973[5];
static int32_t r974[5];
static int32_t r975[30];
static int32_t r976[1];
static int32_t r977[1];
static int32_t r978[5];
static int32_t r979[5];
static int32_t r980[1];
static int32_t r981[5];
static int32_t r982[5];
static int32_t r983[5];
static int32_t r984[30];
static int32_t r985[30];
static int32_t r986[5];
static int32_t r987[30];
static int32_t r988[5];
static int32_t r989[30];
static int32_t r990[30];
static int32_t r991[5];
static int32_t r992[5];
static uint8_t r993[5];
static int32_t r994[5];
static int32_t r995[5];
static int32_t r996[1];
static int32_t r997[5];
static int32_t r998[5];
static int32_t r999[5];
static int32_t r1000[5];
static int32_t r1001[1];
static int32_t r1002[5];
static int32_t r1003[1];
static int32_t r1004[5];
static int32_t r1005[1];
static int32_t r1006[1];
static int32_t r1007[1];
static int32_t r1008[1];
static int32_t r1009[20];
static int32_t r1010[20];
static int32_t r1011[80];
static int32_t r1012[80];
static int32_t r1013[5];
static int32_t r1014[5];
static int32_t r1015[16];
static int32_t r1016[16];
static int32_t r1017[80];
static uint8_t r1018[80];
static const int32_t *const r1019 = rom28_lit;
static int32_t r1020[80];
static int32_t r1021[80];
static int32_t r1022[80];
static int32_t r1023[80];
static int32_t r1024[80];
static int32_t r1025[400];
static int32_t r1026[1];
static int32_t r1027[400];
static int32_t r1028[1];
static int32_t r1029[400];
static int32_t r1030[400];
static int32_t r1031[1];
static int32_t r1032[400];
static int32_t r1033[1];
static int32_t r1034[400];
static int32_t r1035[400];
static int32_t r1036[25];
static int32_t r1037[25];
static int32_t r1038[400];
static int32_t r1039[1];
static int32_t r1040[1];
static int32_t r1041[25];
static int32_t r1042[25];
static int32_t r1043[1];
static int32_t r1044[25];
static int32_t r1045[25];
static int32_t r1046[25];
static int32_t r1047[400];
static int32_t r1048[400];
static int32_t r1049[25];
static int32_t r1050[400];
static int32_t r1051[25];
static int32_t r1052[400];
static int32_t r1053[400];
static int32_t r1054[25];
static int32_t r1055[25];
static uint8_t r1056[25];
static int32_t r1057[25];
static int32_t r1058[25];
static int32_t r1059[1];
static int32_t r1060[25];
static int32_t r1061[25];
static int32_t r1062[400];
static int32_t r1063[25];
static int32_t r1064[25];
static int32_t r1065[400];
static int32_t r1066[1];
static int32_t r1067[1];
static int32_t r1068[25];
static int32_t r1069[25];
static int32_t r1070[1];
static int32_t r1071[25];
static int32_t r1072[25];
static int32_t r1073[25];
static int32_t r1074[400];
static int32_t r1075[400];
static int32_t r1076[25];
static int32_t r1077[400];
static int32_t r1078[25];
static int32_t r1079[400];
static int32_t r1080[400];
static int32_t r1081[25];
static int32_t r1082[25];
static uint8_t r1083[25];
static int32_t r1084[25];
static int32_t r1085[25];
static int32_t r1086[1];
static int32_t r1087[25];
static int32_t r1088[25];
static int32_t r1089[25];
static int32_t r1090[25];
static int32_t r1091[1];
static int32_t r1092[25];
static int32_t r1093[25];
static int32_t r1094[1];
static uint8_t r1095[25];
static int32_t r1096[1];
static int32_t r1097[25];
static int32_t r1098[25];
static int32_t r1099[5];
static const int32_t *const r1100 = rom29_lit;
static int32_t r1101[5];
static uint8_t r1102[1];
static int32_t r1103[1];
static int32_t r1104[1];
static int32_t r1105[1];
static int32_t r1106[15];
static int32_t r1107[1];
static int32_t r1108[30];
static int32_t r1109[30];
static int32_t r1110[1];
static int32_t r1111[30];
static int32_t r1112[30];
static uint8_t r1113[30];
static int32_t r1114[30];
static int32_t r1115[30];
static int32_t r1116[30];
static int32_t r1117[30];
static int32_t r1118[30];
static int32_t r1119[30];
static int32_t r1120[30];
static uint8_t r1121[30];
static int32_t r1122[30];
static uint8_t r1123[30];
static int32_t r1124[30];
static int32_t r1125[30];
static int32_t r1126[30];
static int32_t r1127[30];
static int32_t r1128[30];
static int32_t r1129[30];
static int32_t r1130[30];
static uint8_t r1131[30];
static int32_t r1132[30];
static uint8_t r1133[30];
static int32_t r1134[30];
static uint8_t r1135[30];
static int32_t r1136[30];
static uint8_t r1137[30];
static int32_t r1138[30];
static uint8_t r1139[30];
static int32_t r1140[30];
static int32_t r1141[1];
static int32_t r1142[30];
static int32_t r1143[1];
static int32_t r1144[30];
static int32_t r1145[30];
static int32_t r1146[30];
static int32_t r1147[30];
static int32_t r1148[30];
static int32_t r1149[300];
static int32_t r1150[300];
static int32_t r1151[1];
static int32_t r1152[300];
static int32_t r1153[1];
static int32_t r1154[300];
static int32_t r1155[300];
static int32_t r1156[300];
static int32_t r1157[1];
static int32_t r1158[300];
static int32_t r1159[1];
static int32_t r1160[300];
static int32_t r1161[600];
static int32_t r1162[10];
static int32_t r1163[610];
static int32_t r1164[610];
static int32_t r1165[10];
static const int32_t *const r1166 = rom30_lit;
static int32_t r1167[10];
static int32_t r1168[610];
static int32_t r1169[1];
static int32_t r1170[1];
static int32_t r1171[10];
static int32_t r1172[10];
static int32_t r1173[1];
static int32_t r1174[10];
static int32_t r1175[10];
static int32_t r1176[10];
static int32_t r1177[610];
static int32_t r1178[610];
static int32_t r1179[10];
static uint8_t r1180[10];
static int32_t r1181[10];
static int32_t r1182[10];
static int32_t r1183[1];
static int32_t r1184[10];
static int32_t r1185[10];
static int32_t r1186[300];
static int32_t r1187[300];
static int32_t r1188[1];
static int32_t r1189[300];
static int32_t r1190[1];
static int32_t r1191[300];
static int32_t r1192[300];
static int32_t r1193[300];
static int32_t r1194[1];
static int32_t r1195[300];
static int32_t r1196[1];
static int32_t r1197[300];
static int32_t r1198[600];
static int32_t r1199[10];
static int32_t r1200[610];
static int32_t r1201[610];
static int32_t r1202[10];
static int32_t r1203[10];
static int32_t r1204[610];
static int32_t r1205[1];
static int32_t r1206[1];
static int32_t r1207[10];
static int32_t r1208[10];
static int32_t r1209[1];
static int32_t r1210[10];
static int32_t r1211[10];
static int32_t r1212[10];
static int32_t r1213[610];
static int32_t r1214[610];
static int32_t r1215[10];
static uint8_t r1216[10];
static int32_t r1217[10];
static int32_t r1218[10];
static int32_t r1219[1];
static int32_t r1220[10];
static int32_t r1221[10];
static int32_t r1222[10];
static int32_t r1223[10];
static int32_t r1224[20];
static int32_t r1225[10];
static const int32_t *const r1226 = rom31_lit;
static int32_t r1227[10];
static int32_t r1228[20];
static int32_t r1229[1];
static int32_t r1230[1];
static int32_t r1231[10];
static int32_t r1232[10];
static int32_t r1233[1];
static int32_t r1234[10];
static int32_t r1235[10];
static int32_t r1236[10];
static int32_t r1237[20];
static int32_t r1238[20];
static int32_t r1239[10];
static uint8_t r1240[10];
static int32_t r1241[10];
static int32_t r1242[10];
static int32_t r1243[1];
static int32_t r1244[10];
static int32_t r1245[10];
static int32_t r1246[10];
static int32_t r1247[10];
static int32_t r1248[10];
static int32_t r1249[10];
static int32_t r1250[10];

static void program_run(void) {
    /* abs [abs] -> r26 */
    for (long i1 = 0; i1 < 160; ++i1) {
        r26[i1] = abs32(r16[i1]);
    }
    /* reduce_max [reduce_max] -> r27 */
    for (long i2 = 0; i2 < 1; ++i2) {
        r27[i2] = (-2147483647 - 1);
    }
    for (long i3 = 0; i3 < 160; ++i3) {
        long t5 = i3;
        long c40 = t5 / 160; t5 %= 160;
        long c41 = t5;
        r27[c40 * 1] = max32(r27[c40 * 1], r26[i3]);
    }
    /* max [max] -> r28 */
    for (long i6 = 0; i6 < 1; ++i6) {
        r28[i6] = max32(r13[i6], r27[i6]);
    }
    /* concat [concatenate] -> r29 */
    for (long i7 = 0; i7 < 15; ++i7) {
        long t9 = i7;
        long c80 = t9 / 15; t9 %= 15;
        long c81 = t9;
        r29[c80 * 175 + (c81 + 0) * 1] = r0[i7];
    }
    for (long i10 = 0; i10 < 160; ++i10) {
        long t12 = i10;
        long c110 = t12 / 160; t12 %= 160;
        long c111 = t12;
        r29[c110 * 175 + (c111 + 15) * 1] = r16[i10];
    }
    /* shl [shift_left] -> r31 */
    for (long i13 = 0; i13 < 175; ++i13) {
        r31[i13] = shl32(r29[i13], 1);
    }
    /* rev [rev] -> r32 */
    for (long i14 = 0; i14 < 80; ++i14) {
        long t16 = i14;
        long c150 = t16 / 16; t16 %= 16;
        long c151 = t16;
        r32[i14] = r18[c150 * 16 + (16 - 1 - c151) * 1];
    }
    /* reshape [reshape] -> r33 */
    memcpy(r33, r32, sizeof(int32_t) * 80);
    /* iota [iota] -> r34 */
    for (long i17 = 0; i17 < 160; ++i17) {
        long t19 = i17;
        long c180 = t19;
        r34[i17] = (int32_t)c180;
    }
    /* broadcast [broadcast_in_dim] -> r35 */
    for (long i20 = 0; i20 < 160; ++i20) {
        long t22 = i20;
        long c210 = t22 / 1; t22 %= 1;
        long c211 = t22;
        r35[i20] = r34[c210 * 1];
    }
    /* iota [iota] -> r36 */
    for (long i23 = 0; i23 < 16; ++i23) {
        long t25 = i23;
        long c240 = t25;
        r36[i23] = (int32_t)c240;
    }
    /* broadcast [broadcast_in_dim] -> r37 */
    for (long i26 = 0; i26 < 16; ++i26) {
        long t28 = i26;
        long c270 = t28 / 16; t28 %= 16;
        long c271 = t28;
        r37[i26] = r36[c271 * 1];
    }
    /* add [add] -> r38 */
    for (long i29 = 0; i29 < 2560; ++i29) {
        long t31 = i29;
        long c300 = t31 / 16; t31 %= 16;
        long c301 = t31;
        r38[i29] = add32(r35[c300 * 1], r37[c301 * 1]);
    }
    /* lt [lt] -> r40 */
    for (long i32 = 0; i32 < 2560; ++i32) {
        r40[i32] = r38[i32] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r42 */
    for (long i33 = 0; i33 < 2560; ++i33) {
        r42[i33] = add32(r38[i33], r41[0]);
    }
    /* select_n [select_n] -> r43 */
    for (long i34 = 0; i34 < 2560; ++i34) {
        r43[i34] = r40[i34] == 0 ? r38[i34] : (r42[i34]);
    }
    /* broadcast [broadcast_in_dim] -> r44 */
    for (long i35 = 0; i35 < 2560; ++i35) {
        long t37 = i35;
        long c360 = t37 / 16; t37 %= 16;
        long c361 = t37 / 1; t37 %= 1;
        long c362 = t37;
        r44[i35] = r43[c360 * 16 + c361 * 1];
    }
    /* gather [gather] -> r45 */
    for (long i38 = 0; i38 < 2560; ++i38) {
        long t40 = i38;
        long c390 = t40 / 2560; t40 %= 2560;
        long c391 = t40 / 16; t40 %= 16;
        long c392 = t40;
        long row41 = c391 * 16 + c392 * 1;
        long s42 = clamp_start((long)r44[row41 + 0], 175, 1);
        r45[i38] = r31[c390 * 175 + s42 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r46 */
    for (long i43 = 0; i43 < 2560; ++i43) {
        long t45 = i43;
        long c440 = t45 / 2560; t45 %= 2560;
        long c441 = t45 / 2560; t45 %= 2560;
        long c442 = t45 / 16; t45 %= 16;
        long c443 = t45;
        r46[i43] = r45[c442 * 16 + c443 * 1];
    }
    /* add [add] -> r47 */
    for (long i46 = 0; i46 < 12800; ++i46) {
        long t48 = i46;
        long c470 = t48 / 2560; t48 %= 2560;
        long c471 = t48 / 2560; t48 %= 2560;
        long c472 = t48 / 16; t48 %= 16;
        long c473 = t48;
        r47[i46] = add32(r33[c470 * 16 + c473 * 1], r46[c472 * 16 + c473 * 1]);
    }
    /* convert [convert_element_type] -> r50 */
    for (long i49 = 0; i49 < 1; ++i49) {
        r50[i49] = (int32_t)r48[0];
    }
    /* max [max] -> r51 */
    for (long i50 = 0; i50 < 12800; ++i50) {
        r51[i50] = max32(r50[0], r47[i50]);
    }
    /* convert [convert_element_type] -> r52 */
    for (long i51 = 0; i51 < 1; ++i51) {
        r52[i51] = (int32_t)r49[0];
    }
    /* min [min] -> r53 */
    for (long i52 = 0; i52 < 12800; ++i52) {
        r53[i52] = min32(r52[0], r51[i52]);
    }
    /* sub [sub] -> r54 */
    for (long i53 = 0; i53 < 12800; ++i53) {
        long t55 = i53;
        long c540 = t55 / 2560; t55 %= 2560;
        long c541 = t55 / 2560; t55 %= 2560;
        long c542 = t55 / 16; t55 %= 16;
        long c543 = t55;
        r54[i53] = sub32(r33[c540 * 16 + c543 * 1], r46[c542 * 16 + c543 * 1]);
    }
    /* convert [convert_element_type] -> r55 */
    for (long i56 = 0; i56 < 1; ++i56) {
        r55[i56] = (int32_t)r48[0];
    }
    /* max [max] -> r56 */
    for (long i57 = 0; i57 < 12800; ++i57) {
        r56[i57] = max32(r55[0], r54[i57]);
    }
    /* convert [convert_element_type] -> r57 */
    for (long i58 = 0; i58 < 1; ++i58) {
        r57[i58] = (int32_t)r49[0];
    }
    /* min [min] -> r58 */
    for (long i59 = 0; i59 < 12800; ++i59) {
        r58[i59] = min32(r57[0], r56[i59]);
    }
    /* abs [abs] -> r59 */
    for (long i60 = 0; i60 < 12800; ++i60) {
        r59[i60] = abs32(r53[i60]);
    }
    /* reduce_max [reduce_max] -> r60 */
    for (long i61 = 0; i61 < 800; ++i61) {
        r60[i61] = (-2147483647 - 1);
    }
    for (long i62 = 0; i62 < 12800; ++i62) {
        long t64 = i62;
        long c630 = t64 / 2560; t64 %= 2560;
        long c631 = t64 / 2560; t64 %= 2560;
        long c632 = t64 / 16; t64 %= 16;
        long c633 = t64;
        r60[c630 * 160 + c631 * 160 + c632 * 1] = max32(r60[c630 * 160 + c631 * 160 + c632 * 1], r59[i62]);
    }
    /* sub [sub] -> r62 */
    for (long i65 = 0; i65 < 800; ++i65) {
        r62[i65] = sub32(r60[i65], r61[0]);
    }
    /* loop [scan] -> r84 */
    memcpy(r63, r53, sizeof(int32_t) * 12800);
    memcpy(r64, r61, sizeof(int32_t) * 1);
    memcpy(r65, r39, sizeof(int32_t) * 1);
    memcpy(r66, r62, sizeof(int32_t) * 800);
    memcpy(r67, r60, sizeof(int32_t) * 800);
    for (long t66 = 0; t66 < 12; ++t66) {
        /* add [add] -> r68 */
        for (long i1067 = 0; i1067 < 1; ++i1067) {
            r68[i1067] = add32(r65[0], r30[0]);
        }
        /* add [add] -> r69 */
        for (long i1068 = 0; i1068 < 800; ++i1068) {
            r69[i1068] = add32(r66[i1068], r67[i1068]);
        }
        /* shra [shift_right_arithmetic] -> r70 */
        for (long i1069 = 0; i1069 < 800; ++i1069) {
            r70[i1069] = asr32(r69[i1069], 1);
        }
        /* broadcast [broadcast_in_dim] -> r71 */
        for (long i1070 = 0; i1070 < 800; ++i1070) {
            long t1072 = i1070;
            long c10710 = t1072 / 160; t1072 %= 160;
            long c10711 = t1072 / 160; t1072 %= 160;
            long c10712 = t1072 / 1; t1072 %= 1;
            long c10713 = t1072;
            r71[i1070] = r70[c10710 * 160 + c10712 * 1];
        }
        /* sub [sub] -> r72 */
        for (long i1073 = 0; i1073 < 12800; ++i1073) {
            long t1075 = i1073;
            long c10740 = t1075 / 2560; t1075 %= 2560;
            long c10741 = t1075 / 2560; t1075 %= 2560;
            long c10742 = t1075 / 16; t1075 %= 16;
            long c10743 = t1075;
            r72[i1073] = sub32(r63[c10740 * 2560 + c10742 * 16 + c10743 * 1], r71[c10740 * 160 + c10742 * 1]);
        }
        /* max [max] -> r73 */
        for (long i1076 = 0; i1076 < 12800; ++i1076) {
            r73[i1076] = max32(r72[i1076], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r74 */
        for (long i1077 = 0; i1077 < 800; ++i1077) {
            r74[i1077] = 0;
        }
        for (long i1078 = 0; i1078 < 12800; ++i1078) {
            long t1080 = i1078;
            long c10790 = t1080 / 2560; t1080 %= 2560;
            long c10791 = t1080 / 2560; t1080 %= 2560;
            long c10792 = t1080 / 16; t1080 %= 16;
            long c10793 = t1080;
            r74[c10790 * 160 + c10791 * 160 + c10792 * 1] = add32(r74[c10790 * 160 + c10791 * 160 + c10792 * 1], r73[i1078]);
        }
        /* neg [neg] -> r75 */
        for (long i1081 = 0; i1081 < 12800; ++i1081) {
            r75[i1081] = neg32(r63[i1081]);
        }
        /* broadcast [broadcast_in_dim] -> r76 */
        for (long i1082 = 0; i1082 < 800; ++i1082) {
            long t1084 = i1082;
            long c10830 = t1084 / 160; t1084 %= 160;
            long c10831 = t1084 / 160; t1084 %= 160;
            long c10832 = t1084 / 1; t1084 %= 1;
            long c10833 = t1084;
            r76[i1082] = r70[c10830 * 160 + c10832 * 1];
        }
        /* sub [sub] -> r77 */
        for (long i1085 = 0; i1085 < 12800; ++i1085) {
            long t1087 = i1085;
            long c10860 = t1087 / 2560; t1087 %= 2560;
            long c10861 = t1087 / 2560; t1087 %= 2560;
            long c10862 = t1087 / 16; t1087 %= 16;
            long c10863 = t1087;
            r77[i1085] = sub32(r75[c10860 * 2560 + c10862 * 16 + c10863 * 1], r76[c10860 * 160 + c10862 * 1]);
        }
        /* max [max] -> r78 */
        for (long i1088 = 0; i1088 < 12800; ++i1088) {
            r78[i1088] = max32(r77[i1088], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r79 */
        for (long i1089 = 0; i1089 < 800; ++i1089) {
            r79[i1089] = 0;
        }
        for (long i1090 = 0; i1090 < 12800; ++i1090) {
            long t1092 = i1090;
            long c10910 = t1092 / 2560; t1092 %= 2560;
            long c10911 = t1092 / 2560; t1092 %= 2560;
            long c10912 = t1092 / 16; t1092 %= 16;
            long c10913 = t1092;
            r79[c10910 * 160 + c10911 * 160 + c10912 * 1] = add32(r79[c10910 * 160 + c10911 * 160 + c10912 * 1], r78[i1090]);
        }
        /* add [add] -> r80 */
        for (long i1093 = 0; i1093 < 800; ++i1093) {
            r80[i1093] = add32(r74[i1093], r79[i1093]);
        }
        /* gt [gt] -> r81 */
        for (long i1094 = 0; i1094 < 800; ++i1094) {
            r81[i1094] = r80[i1094] > r64[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r82 */
        for (long i1095 = 0; i1095 < 800; ++i1095) {
            r82[i1095] = r81[i1095] == 0 ? r66[i1095] : (r70[i1095]);
        }
        /* select_n [select_n] -> r83 */
        for (long i1096 = 0; i1096 < 800; ++i1096) {
            r83[i1096] = r81[i1096] == 0 ? r70[i1096] : (r67[i1096]);
        }
        memcpy(r65, r68, sizeof(int32_t) * 1);
        memcpy(r66, r82, sizeof(int32_t) * 800);
        memcpy(r67, r83, sizeof(int32_t) * 800);
    }
    memcpy(r84, r65, sizeof(int32_t) * 1);
    memcpy(r85, r66, sizeof(int32_t) * 800);
    memcpy(r86, r67, sizeof(int32_t) * 800);
    /* abs [abs] -> r87 */
    for (long i1097 = 0; i1097 < 12800; ++i1097) {
        r87[i1097] = abs32(r58[i1097]);
    }
    /* reduce_max [reduce_max] -> r88 */
    for (long i1098 = 0; i1098 < 800; ++i1098) {
        r88[i1098] = (-2147483647 - 1);
    }
    for (long i1099 = 0; i1099 < 12800; ++i1099) {
        long t1101 = i1099;
        long c11000 = t1101 / 2560; t1101 %= 2560;
        long c11001 = t1101 / 2560; t1101 %= 2560;
        long c11002 = t1101 / 16; t1101 %= 16;
        long c11003 = t1101;
        r88[c11000 * 160 + c11001 * 160 + c11002 * 1] = max32(r88[c11000 * 160 + c11001 * 160 + c11002 * 1], r87[i1099]);
    }
    /* sub [sub] -> r89 */
    for (long i1102 = 0; i1102 < 800; ++i1102) {
        r89[i1102] = sub32(r88[i1102], r61[0]);
    }
    /* loop [scan] -> r111 */
    memcpy(r90, r58, sizeof(int32_t) * 12800);
    memcpy(r91, r61, sizeof(int32_t) * 1);
    memcpy(r92, r39, sizeof(int32_t) * 1);
    memcpy(r93, r89, sizeof(int32_t) * 800);
    memcpy(r94, r88, sizeof(int32_t) * 800);
    for (long t1103 = 0; t1103 < 12; ++t1103) {
        /* add [add] -> r95 */
        for (long i2104 = 0; i2104 < 1; ++i2104) {
            r95[i2104] = add32(r92[0], r30[0]);
        }
        /* add [add] -> r96 */
        for (long i2105 = 0; i2105 < 800; ++i2105) {
            r96[i2105] = add32(r93[i2105], r94[i2105]);
        }
        /* shra [shift_right_arithmetic] -> r97 */
        for (long i2106 = 0; i2106 < 800; ++i2106) {
            r97[i2106] = asr32(r96[i2106], 1);
        }
        /* broadcast [broadcast_in_dim] -> r98 */
        for (long i2107 = 0; i2107 < 800; ++i2107) {
            long t2109 = i2107;
            long c21080 = t2109 / 160; t2109 %= 160;
            long c21081 = t2109 / 160; t2109 %= 160;
            long c21082 = t2109 / 1; t2109 %= 1;
            long c21083 = t2109;
            r98[i2107] = r97[c21080 * 160 + c21082 * 1];
        }
        /* sub [sub] -> r99 */
        for (long i2110 = 0; i2110 < 12800; ++i2110) {
            long t2112 = i2110;
            long c21110 = t2112 / 2560; t2112 %= 2560;
            long c21111 = t2112 / 2560; t2112 %= 2560;
            long c21112 = t2112 / 16; t2112 %= 16;
            long c21113 = t2112;
            r99[i2110] = sub32(r90[c21110 * 2560 + c21112 * 16 + c21113 * 1], r98[c21110 * 160 + c21112 * 1]);
        }
        /* max [max] -> r100 */
        for (long i2113 = 0; i2113 < 12800; ++i2113) {
            r100[i2113] = max32(r99[i2113], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r101 */
        for (long i2114 = 0; i2114 < 800; ++i2114) {
            r101[i2114] = 0;
        }
        for (long i2115 = 0; i2115 < 12800; ++i2115) {
            long t2117 = i2115;
            long c21160 = t2117 / 2560; t2117 %= 2560;
            long c21161 = t2117 / 2560; t2117 %= 2560;
            long c21162 = t2117 / 16; t2117 %= 16;
            long c21163 = t2117;
            r101[c21160 * 160 + c21161 * 160 + c21162 * 1] = add32(r101[c21160 * 160 + c21161 * 160 + c21162 * 1], r100[i2115]);
        }
        /* neg [neg] -> r102 */
        for (long i2118 = 0; i2118 < 12800; ++i2118) {
            r102[i2118] = neg32(r90[i2118]);
        }
        /* broadcast [broadcast_in_dim] -> r103 */
        for (long i2119 = 0; i2119 < 800; ++i2119) {
            long t2121 = i2119;
            long c21200 = t2121 / 160; t2121 %= 160;
            long c21201 = t2121 / 160; t2121 %= 160;
            long c21202 = t2121 / 1; t2121 %= 1;
            long c21203 = t2121;
            r103[i2119] = r97[c21200 * 160 + c21202 * 1];
        }
        /* sub [sub] -> r104 */
        for (long i2122 = 0; i2122 < 12800; ++i2122) {
            long t2124 = i2122;
            long c21230 = t2124 / 2560; t2124 %= 2560;
            long c21231 = t2124 / 2560; t2124 %= 2560;
            long c21232 = t2124 / 16; t2124 %= 16;
            long c21233 = t2124;
            r104[i2122] = sub32(r102[c21230 * 2560 + c21232 * 16 + c21233 * 1], r103[c21230 * 160 + c21232 * 1]);
        }
        /* max [max] -> r105 */
        for (long i2125 = 0; i2125 < 12800; ++i2125) {
            r105[i2125] = max32(r104[i2125], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r106 */
        for (long i2126 = 0; i2126 < 800; ++i2126) {
            r106[i2126] = 0;
        }
        for (long i2127 = 0; i2127 < 12800; ++i2127) {
            long t2129 = i2127;
            long c21280 = t2129 / 2560; t2129 %= 2560;
            long c21281 = t2129 / 2560; t2129 %= 2560;
            long c21282 = t2129 / 16; t2129 %= 16;
            long c21283 = t2129;
            r106[c21280 * 160 + c21281 * 160 + c21282 * 1] = add32(r106[c21280 * 160 + c21281 * 160 + c21282 * 1], r105[i2127]);
        }
        /* add [add] -> r107 */
        for (long i2130 = 0; i2130 < 800; ++i2130) {
            r107[i2130] = add32(r101[i2130], r106[i2130]);
        }
        /* gt [gt] -> r108 */
        for (long i2131 = 0; i2131 < 800; ++i2131) {
            r108[i2131] = r107[i2131] > r91[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r109 */
        for (long i2132 = 0; i2132 < 800; ++i2132) {
            r109[i2132] = r108[i2132] == 0 ? r93[i2132] : (r97[i2132]);
        }
        /* select_n [select_n] -> r110 */
        for (long i2133 = 0; i2133 < 800; ++i2133) {
            r110[i2133] = r108[i2133] == 0 ? r97[i2133] : (r94[i2133]);
        }
        memcpy(r92, r95, sizeof(int32_t) * 1);
        memcpy(r93, r109, sizeof(int32_t) * 800);
        memcpy(r94, r110, sizeof(int32_t) * 800);
    }
    memcpy(r111, r92, sizeof(int32_t) * 1);
    memcpy(r112, r93, sizeof(int32_t) * 800);
    memcpy(r113, r94, sizeof(int32_t) * 800);
    /* sub [sub] -> r114 */
    for (long i2134 = 0; i2134 < 800; ++i2134) {
        r114[i2134] = sub32(r86[i2134], r113[i2134]);
    }
    /* transpose [transpose] -> r115 */
    for (long i2135 = 0; i2135 < 800; ++i2135) {
        long t2137 = i2135;
        long c21360 = t2137 / 800; t2137 %= 800;
        long c21361 = t2137 / 160; t2137 %= 160;
        long c21362 = t2137;
        r115[i2135] = r114[c21360 * 160 + c21361 * 160 + c21362 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r116 */
    for (long i2138 = 0; i2138 < 1; ++i2138) {
        long t2140 = i2138;
        long c21390 = t2140 / 1; t2140 %= 1;
        long c21391 = t2140;
        r116[i2138] = r17[0];
    }
    /* max [max] -> r117 */
    for (long i2141 = 0; i2141 < 800; ++i2141) {
        r117[i2141] = max32(r115[i2141], r39[0]);
    }
    /* iota [iota] -> r118 */
    for (long i2142 = 0; i2142 < 800; ++i2142) {
        long t2144 = i2142;
        long c21430 = t2144 / 800; t2144 %= 800;
        long c21431 = t2144 / 160; t2144 %= 160;
        long c21432 = t2144;
        r118[i2142] = (int32_t)c21432;
    }
    /* broadcast [broadcast_in_dim] -> r119 */
    for (long i2145 = 0; i2145 < 1; ++i2145) {
        long t2147 = i2145;
        long c21460 = t2147 / 1; t2147 %= 1;
        long c21461 = t2147 / 1; t2147 %= 1;
        long c21462 = t2147;
        r119[i2145] = r116[0];
    }
    /* lt [lt] -> r120 */
    for (long i2148 = 0; i2148 < 800; ++i2148) {
        long t2150 = i2148;
        long c21490 = t2150 / 800; t2150 %= 800;
        long c21491 = t2150 / 160; t2150 %= 160;
        long c21492 = t2150;
        r120[i2148] = r118[c21491 * 160 + c21492 * 1] < r119[0] ? 1 : 0;
    }
    /* convert [convert_element_type] -> r121 */
    for (long i2151 = 0; i2151 < 1; ++i2151) {
        r121[i2151] = (int32_t)r39[0];
    }
    /* broadcast [broadcast_in_dim] -> r122 */
    for (long i2152 = 0; i2152 < 800; ++i2152) {
        long t2154 = i2152;
        long c21530 = t2154 / 800; t2154 %= 800;
        long c21531 = t2154 / 160; t2154 %= 160;
        long c21532 = t2154;
        r122[i2152] = r121[0];
    }
    /* select_n [select_n] -> r123 */
    for (long i2155 = 0; i2155 < 800; ++i2155) {
        r123[i2155] = r120[i2155] == 0 ? r122[i2155] : (r117[i2155]);
    }
    /* reduce_sum [reduce_sum] -> r124 */
    for (long i2156 = 0; i2156 < 5; ++i2156) {
        r124[i2156] = 0;
    }
    for (long i2157 = 0; i2157 < 800; ++i2157) {
        long t2159 = i2157;
        long c21580 = t2159 / 800; t2159 %= 800;
        long c21581 = t2159 / 160; t2159 %= 160;
        long c21582 = t2159;
        r124[c21580 * 5 + c21581 * 1] = add32(r124[c21580 * 5 + c21581 * 1], r123[i2157]);
    }
    /* shl [shift_left] -> r125 */
    for (long i2160 = 0; i2160 < 5; ++i2160) {
        r125[i2160] = shl32(r124[i2160], 0);
    }
    /* lt [lt] -> r126 */
    for (long i2161 = 0; i2161 < 1; ++i2161) {
        r126[i2161] = r17[i2161] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r127 */
    for (long i2162 = 0; i2162 < 1; ++i2162) {
        r127[i2162] = add32(r17[i2162], r41[0]);
    }
    /* select_n [select_n] -> r128 */
    for (long i2163 = 0; i2163 < 1; ++i2163) {
        r128[i2163] = r126[i2163] == 0 ? r17[i2163] : (r127[i2163]);
    }
    /* broadcast [broadcast_in_dim] -> r129 */
    for (long i2164 = 0; i2164 < 1; ++i2164) {
        long t2166 = i2164;
        long c21650 = t2166 / 1; t2166 %= 1;
        long c21651 = t2166;
        r129[i2164] = r128[0];
    }
    /* gather [gather] -> r130 */
    for (long i2167 = 0; i2167 < 15; ++i2167) {
        long t2169 = i2167;
        long c21680 = t2169 / 15; t2169 %= 15;
        long c21681 = t2169;
        long row2170 = c21680 * 1;
        long s2171 = clamp_start((long)r129[row2170 + 0], 175, 15);
        r130[i2167] = r29[c21680 * 175 + (s2171 + c21681) * 1];
    }
    /* add [add] -> r131 */
    for (long i2172 = 0; i2172 < 1; ++i2172) {
        r131[i2172] = add32(r6[i2172], r17[i2172]);
    }
    /* and [and] -> r132 */
    for (long i2173 = 0; i2173 < 1; ++i2173) {
        r132[i2173] = r6[i2173] & r30[0];
    }
    /* slice [slice] -> r133 */
    for (long i2174 = 0; i2174 < 165; ++i2174) {
        long t2176 = i2174;
        long c21750 = t2176 / 165; t2176 %= 165;
        long c21751 = t2176;
        r133[i2174] = r29[(0 + c21750 * 1) * 175 + (10 + c21751 * 1) * 1];
    }
    /* shl [shift_left] -> r134 */
    for (long i2177 = 0; i2177 < 165; ++i2177) {
        r134[i2177] = shl32(r133[i2177], 1);
    }
    /* convert [convert_element_type] -> r135 */
    for (long i2178 = 0; i2178 < 1; ++i2178) {
        r135[i2178] = (int32_t)r39[0];
    }
    /* pad [pad] -> r136 */
    for (long i2179 = 0; i2179 < 166; ++i2179) {
        r136[i2179] = r135[0];
    }
    for (long i2180 = 0; i2180 < 165; ++i2180) {
        long t2182 = i2180;
        long c21810 = t2182 / 165; t2182 %= 165;
        long c21811 = t2182;
        long d2183 = 0 + c21810 * 1;
        long d2184 = 0 + c21811 * 1;
        if (d2183 >= 0 && d2183 < 1 && d2184 >= 0 && d2184 < 166) r136[d2183 * 166 + d2184 * 1] = r134[i2180];
    }
    /* iota [iota] -> r137 */
    for (long i2185 = 0; i2185 < 80; ++i2185) {
        long t2187 = i2185;
        long c21860 = t2187;
        r137[i2185] = (int32_t)c21860;
    }
    /* shl [shift_left] -> r138 */
    for (long i2188 = 0; i2188 < 80; ++i2188) {
        r138[i2188] = shl32(r137[i2188], 1);
    }
    /* broadcast [broadcast_in_dim] -> r139 */
    for (long i2189 = 0; i2189 < 80; ++i2189) {
        long t2191 = i2189;
        long c21900 = t2191 / 1; t2191 %= 1;
        long c21901 = t2191;
        r139[i2189] = r138[c21900 * 1];
    }
    /* iota [iota] -> r140 */
    for (long i2192 = 0; i2192 < 6; ++i2192) {
        long t2194 = i2192;
        long c21930 = t2194;
        r140[i2192] = (int32_t)c21930;
    }
    /* broadcast [broadcast_in_dim] -> r141 */
    for (long i2195 = 0; i2195 < 6; ++i2195) {
        long t2197 = i2195;
        long c21960 = t2197 / 6; t2197 %= 6;
        long c21961 = t2197;
        r141[i2195] = r140[c21961 * 1];
    }
    /* add [add] -> r142 */
    for (long i2198 = 0; i2198 < 480; ++i2198) {
        long t2200 = i2198;
        long c21990 = t2200 / 6; t2200 %= 6;
        long c21991 = t2200;
        r142[i2198] = add32(r139[c21990 * 1], r141[c21991 * 1]);
    }
    /* broadcast [broadcast_in_dim] -> r143 */
    for (long i2201 = 0; i2201 < 480; ++i2201) {
        long t2203 = i2201;
        long c22020 = t2203 / 480; t2203 %= 480;
        long c22021 = t2203 / 6; t2203 %= 6;
        long c22022 = t2203;
        r143[i2201] = r142[c22021 * 6 + c22022 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r144 */
    for (long i2204 = 0; i2204 < 1; ++i2204) {
        long t2206 = i2204;
        long c22050 = t2206 / 1; t2206 %= 1;
        long c22051 = t2206 / 1; t2206 %= 1;
        long c22052 = t2206;
        r144[i2204] = r132[0];
    }
    /* add [add] -> r145 */
    for (long i2207 = 0; i2207 < 480; ++i2207) {
        long t2209 = i2207;
        long c22080 = t2209 / 480; t2209 %= 480;
        long c22081 = t2209 / 6; t2209 %= 6;
        long c22082 = t2209;
        r145[i2207] = add32(r144[0], r143[c22081 * 6 + c22082 * 1]);
    }
    /* lt [lt] -> r146 */
    for (long i2210 = 0; i2210 < 480; ++i2210) {
        r146[i2210] = r145[i2210] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r148 */
    for (long i2211 = 0; i2211 < 480; ++i2211) {
        r148[i2211] = add32(r145[i2211], r147[0]);
    }
    /* select_n [select_n] -> r149 */
    for (long i2212 = 0; i2212 < 480; ++i2212) {
        r149[i2212] = r146[i2212] == 0 ? r145[i2212] : (r148[i2212]);
    }
    /* broadcast [broadcast_in_dim] -> r150 */
    for (long i2213 = 0; i2213 < 480; ++i2213) {
        long t2215 = i2213;
        long c22140 = t2215 / 480; t2215 %= 480;
        long c22141 = t2215 / 6; t2215 %= 6;
        long c22142 = t2215 / 1; t2215 %= 1;
        long c22143 = t2215;
        r150[i2213] = r149[c22141 * 6 + c22142 * 1];
    }
    /* gather [gather] -> r151 */
    for (long i2216 = 0; i2216 < 480; ++i2216) {
        long t2218 = i2216;
        long c22170 = t2218 / 480; t2218 %= 480;
        long c22171 = t2218 / 6; t2218 %= 6;
        long c22172 = t2218;
        long row2219 = c22170 * 480 + c22171 * 6 + c22172 * 1;
        long s2220 = clamp_start((long)r150[row2219 + 0], 166, 1);
        r151[i2216] = r136[c22170 * 166 + s2220 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r152 */
    for (long i2221 = 0; i2221 < 6; ++i2221) {
        long t2223 = i2221;
        long c22220 = t2223 / 6; t2223 %= 6;
        long c22221 = t2223 / 6; t2223 %= 6;
        long c22222 = t2223;
        r152[i2221] = r19[c22222 * 1];
    }
    /* add [add] -> r153 */
    for (long i2224 = 0; i2224 < 480; ++i2224) {
        long t2226 = i2224;
        long c22250 = t2226 / 480; t2226 %= 480;
        long c22251 = t2226 / 6; t2226 %= 6;
        long c22252 = t2226;
        r153[i2224] = add32(r152[c22252 * 1], r151[c22251 * 6 + c22252 * 1]);
    }
    /* convert [convert_element_type] -> r154 */
    for (long i2227 = 0; i2227 < 1; ++i2227) {
        r154[i2227] = (int32_t)r48[0];
    }
    /* max [max] -> r155 */
    for (long i2228 = 0; i2228 < 480; ++i2228) {
        r155[i2228] = max32(r154[0], r153[i2228]);
    }
    /* convert [convert_element_type] -> r156 */
    for (long i2229 = 0; i2229 < 1; ++i2229) {
        r156[i2229] = (int32_t)r49[0];
    }
    /* min [min] -> r157 */
    for (long i2230 = 0; i2230 < 480; ++i2230) {
        r157[i2230] = min32(r156[0], r155[i2230]);
    }
    /* broadcast [broadcast_in_dim] -> r158 */
    for (long i2231 = 0; i2231 < 6; ++i2231) {
        long t2233 = i2231;
        long c22320 = t2233 / 6; t2233 %= 6;
        long c22321 = t2233 / 6; t2233 %= 6;
        long c22322 = t2233;
        r158[i2231] = r19[c22322 * 1];
    }
    /* sub [sub] -> r159 */
    for (long i2234 = 0; i2234 < 480; ++i2234) {
        long t2236 = i2234;
        long c22350 = t2236 / 480; t2236 %= 480;
        long c22351 = t2236 / 6; t2236 %= 6;
        long c22352 = t2236;
        r159[i2234] = sub32(r158[c22352 * 1], r151[c22351 * 6 + c22352 * 1]);
    }
    /* convert [convert_element_type] -> r160 */
    for (long i2237 = 0; i2237 < 1; ++i2237) {
        r160[i2237] = (int32_t)r48[0];
    }
    /* max [max] -> r161 */
    for (long i2238 = 0; i2238 < 480; ++i2238) {
        r161[i2238] = max32(r160[0], r159[i2238]);
    }
    /* convert [convert_element_type] -> r162 */
    for (long i2239 = 0; i2239 < 1; ++i2239) {
        r162[i2239] = (int32_t)r49[0];
    }
    /* min [min] -> r163 */
    for (long i2240 = 0; i2240 < 480; ++i2240) {
        r163[i2240] = min32(r162[0], r161[i2240]);
    }
    /* abs [abs] -> r164 */
    for (long i2241 = 0; i2241 < 480; ++i2241) {
        r164[i2241] = abs32(r157[i2241]);
    }
    /* reduce_max [reduce_max] -> r165 */
    for (long i2242 = 0; i2242 < 80; ++i2242) {
        r165[i2242] = (-2147483647 - 1);
    }
    for (long i2243 = 0; i2243 < 480; ++i2243) {
        long t2245 = i2243;
        long c22440 = t2245 / 480; t2245 %= 480;
        long c22441 = t2245 / 6; t2245 %= 6;
        long c22442 = t2245;
        r165[c22440 * 80 + c22441 * 1] = max32(r165[c22440 * 80 + c22441 * 1], r164[i2243]);
    }
    /* sub [sub] -> r166 */
    for (long i2246 = 0; i2246 < 80; ++i2246) {
        r166[i2246] = sub32(r165[i2246], r61[0]);
    }
    /* loop [scan] -> r188 */
    memcpy(r167, r157, sizeof(int32_t) * 480);
    memcpy(r168, r61, sizeof(int32_t) * 1);
    memcpy(r169, r39, sizeof(int32_t) * 1);
    memcpy(r170, r166, sizeof(int32_t) * 80);
    memcpy(r171, r165, sizeof(int32_t) * 80);
    for (long t2247 = 0; t2247 < 12; ++t2247) {
        /* add [add] -> r172 */
        for (long i3248 = 0; i3248 < 1; ++i3248) {
            r172[i3248] = add32(r169[0], r30[0]);
        }
        /* add [add] -> r173 */
        for (long i3249 = 0; i3249 < 80; ++i3249) {
            r173[i3249] = add32(r170[i3249], r171[i3249]);
        }
        /* shra [shift_right_arithmetic] -> r174 */
        for (long i3250 = 0; i3250 < 80; ++i3250) {
            r174[i3250] = asr32(r173[i3250], 1);
        }
        /* broadcast [broadcast_in_dim] -> r175 */
        for (long i3251 = 0; i3251 < 80; ++i3251) {
            long t3253 = i3251;
            long c32520 = t3253 / 80; t3253 %= 80;
            long c32521 = t3253 / 1; t3253 %= 1;
            long c32522 = t3253;
            r175[i3251] = r174[c32521 * 1];
        }
        /* sub [sub] -> r176 */
        for (long i3254 = 0; i3254 < 480; ++i3254) {
            long t3256 = i3254;
            long c32550 = t3256 / 480; t3256 %= 480;
            long c32551 = t3256 / 6; t3256 %= 6;
            long c32552 = t3256;
            r176[i3254] = sub32(r167[c32551 * 6 + c32552 * 1], r175[c32551 * 1]);
        }
        /* max [max] -> r177 */
        for (long i3257 = 0; i3257 < 480; ++i3257) {
            r177[i3257] = max32(r176[i3257], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r178 */
        for (long i3258 = 0; i3258 < 80; ++i3258) {
            r178[i3258] = 0;
        }
        for (long i3259 = 0; i3259 < 480; ++i3259) {
            long t3261 = i3259;
            long c32600 = t3261 / 480; t3261 %= 480;
            long c32601 = t3261 / 6; t3261 %= 6;
            long c32602 = t3261;
            r178[c32600 * 80 + c32601 * 1] = add32(r178[c32600 * 80 + c32601 * 1], r177[i3259]);
        }
        /* neg [neg] -> r179 */
        for (long i3262 = 0; i3262 < 480; ++i3262) {
            r179[i3262] = neg32(r167[i3262]);
        }
        /* broadcast [broadcast_in_dim] -> r180 */
        for (long i3263 = 0; i3263 < 80; ++i3263) {
            long t3265 = i3263;
            long c32640 = t3265 / 80; t3265 %= 80;
            long c32641 = t3265 / 1; t3265 %= 1;
            long c32642 = t3265;
            r180[i3263] = r174[c32641 * 1];
        }
        /* sub [sub] -> r181 */
        for (long i3266 = 0; i3266 < 480; ++i3266) {
            long t3268 = i3266;
            long c32670 = t3268 / 480; t3268 %= 480;
            long c32671 = t3268 / 6; t3268 %= 6;
            long c32672 = t3268;
            r181[i3266] = sub32(r179[c32671 * 6 + c32672 * 1], r180[c32671 * 1]);
        }
        /* max [max] -> r182 */
        for (long i3269 = 0; i3269 < 480; ++i3269) {
            r182[i3269] = max32(r181[i3269], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r183 */
        for (long i3270 = 0; i3270 < 80; ++i3270) {
            r183[i3270] = 0;
        }
        for (long i3271 = 0; i3271 < 480; ++i3271) {
            long t3273 = i3271;
            long c32720 = t3273 / 480; t3273 %= 480;
            long c32721 = t3273 / 6; t3273 %= 6;
            long c32722 = t3273;
            r183[c32720 * 80 + c32721 * 1] = add32(r183[c32720 * 80 + c32721 * 1], r182[i3271]);
        }
        /* add [add] -> r184 */
        for (long i3274 = 0; i3274 < 80; ++i3274) {
            r184[i3274] = add32(r178[i3274], r183[i3274]);
        }
        /* gt [gt] -> r185 */
        for (long i3275 = 0; i3275 < 80; ++i3275) {
            r185[i3275] = r184[i3275] > r168[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r186 */
        for (long i3276 = 0; i3276 < 80; ++i3276) {
            r186[i3276] = r185[i3276] == 0 ? r170[i3276] : (r174[i3276]);
        }
        /* select_n [select_n] -> r187 */
        for (long i3277 = 0; i3277 < 80; ++i3277) {
            r187[i3277] = r185[i3277] == 0 ? r174[i3277] : (r171[i3277]);
        }
        memcpy(r169, r172, sizeof(int32_t) * 1);
        memcpy(r170, r186, sizeof(int32_t) * 80);
        memcpy(r171, r187, sizeof(int32_t) * 80);
    }
    memcpy(r188, r169, sizeof(int32_t) * 1);
    memcpy(r189, r170, sizeof(int32_t) * 80);
    memcpy(r190, r171, sizeof(int32_t) * 80);
    /* abs [abs] -> r191 */
    for (long i3278 = 0; i3278 < 480; ++i3278) {
        r191[i3278] = abs32(r163[i3278]);
    }
    /* reduce_max [reduce_max] -> r192 */
    for (long i3279 = 0; i3279 < 80; ++i3279) {
        r192[i3279] = (-2147483647 - 1);
    }
    for (long i3280 = 0; i3280 < 480; ++i3280) {
        long t3282 = i3280;
        long c32810 = t3282 / 480; t3282 %= 480;
        long c32811 = t3282 / 6; t3282 %= 6;
        long c32812 = t3282;
        r192[c32810 * 80 + c32811 * 1] = max32(r192[c32810 * 80 + c32811 * 1], r191[i3280]);
    }
    /* sub [sub] -> r193 */
    for (long i3283 = 0; i3283 < 80; ++i3283) {
        r193[i3283] = sub32(r192[i3283], r61[0]);
    }
    /* loop [scan] -> r215 */
    memcpy(r194, r163, sizeof(int32_t) * 480);
    memcpy(r195, r61, sizeof(int32_t) * 1);
    memcpy(r196, r39, sizeof(int32_t) * 1);
    memcpy(r197, r193, sizeof(int32_t) * 80);
    memcpy(r198, r192, sizeof(int32_t) * 80);
    for (long t3284 = 0; t3284 < 12; ++t3284) {
        /* add [add] -> r199 */
        for (long i4285 = 0; i4285 < 1; ++i4285) {
            r199[i4285] = add32(r196[0], r30[0]);
        }
        /* add [add] -> r200 */
        for (long i4286 = 0; i4286 < 80; ++i4286) {
            r200[i4286] = add32(r197[i4286], r198[i4286]);
        }
        /* shra [shift_right_arithmetic] -> r201 */
        for (long i4287 = 0; i4287 < 80; ++i4287) {
            r201[i4287] = asr32(r200[i4287], 1);
        }
        /* broadcast [broadcast_in_dim] -> r202 */
        for (long i4288 = 0; i4288 < 80; ++i4288) {
            long t4290 = i4288;
            long c42890 = t4290 / 80; t4290 %= 80;
            long c42891 = t4290 / 1; t4290 %= 1;
            long c42892 = t4290;
            r202[i4288] = r201[c42891 * 1];
        }
        /* sub [sub] -> r203 */
        for (long i4291 = 0; i4291 < 480; ++i4291) {
            long t4293 = i4291;
            long c42920 = t4293 / 480; t4293 %= 480;
            long c42921 = t4293 / 6; t4293 %= 6;
            long c42922 = t4293;
            r203[i4291] = sub32(r194[c42921 * 6 + c42922 * 1], r202[c42921 * 1]);
        }
        /* max [max] -> r204 */
        for (long i4294 = 0; i4294 < 480; ++i4294) {
            r204[i4294] = max32(r203[i4294], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r205 */
        for (long i4295 = 0; i4295 < 80; ++i4295) {
            r205[i4295] = 0;
        }
        for (long i4296 = 0; i4296 < 480; ++i4296) {
            long t4298 = i4296;
            long c42970 = t4298 / 480; t4298 %= 480;
            long c42971 = t4298 / 6; t4298 %= 6;
            long c42972 = t4298;
            r205[c42970 * 80 + c42971 * 1] = add32(r205[c42970 * 80 + c42971 * 1], r204[i4296]);
        }
        /* neg [neg] -> r206 */
        for (long i4299 = 0; i4299 < 480; ++i4299) {
            r206[i4299] = neg32(r194[i4299]);
        }
        /* broadcast [broadcast_in_dim] -> r207 */
        for (long i4300 = 0; i4300 < 80; ++i4300) {
            long t4302 = i4300;
            long c43010 = t4302 / 80; t4302 %= 80;
            long c43011 = t4302 / 1; t4302 %= 1;
            long c43012 = t4302;
            r207[i4300] = r201[c43011 * 1];
        }
        /* sub [sub] -> r208 */
        for (long i4303 = 0; i4303 < 480; ++i4303) {
            long t4305 = i4303;
            long c43040 = t4305 / 480; t4305 %= 480;
            long c43041 = t4305 / 6; t4305 %= 6;
            long c43042 = t4305;
            r208[i4303] = sub32(r206[c43041 * 6 + c43042 * 1], r207[c43041 * 1]);
        }
        /* max [max] -> r209 */
        for (long i4306 = 0; i4306 < 480; ++i4306) {
            r209[i4306] = max32(r208[i4306], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r210 */
        for (long i4307 = 0; i4307 < 80; ++i4307) {
            r210[i4307] = 0;
        }
        for (long i4308 = 0; i4308 < 480; ++i4308) {
            long t4310 = i4308;
            long c43090 = t4310 / 480; t4310 %= 480;
            long c43091 = t4310 / 6; t4310 %= 6;
            long c43092 = t4310;
            r210[c43090 * 80 + c43091 * 1] = add32(r210[c43090 * 80 + c43091 * 1], r209[i4308]);
        }
        /* add [add] -> r211 */
        for (long i4311 = 0; i4311 < 80; ++i4311) {
            r211[i4311] = add32(r205[i4311], r210[i4311]);
        }
        /* gt [gt] -> r212 */
        for (long i4312 = 0; i4312 < 80; ++i4312) {
            r212[i4312] = r211[i4312] > r195[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r213 */
        for (long i4313 = 0; i4313 < 80; ++i4313) {
            r213[i4313] = r212[i4313] == 0 ? r197[i4313] : (r201[i4313]);
        }
        /* select_n [select_n] -> r214 */
        for (long i4314 = 0; i4314 < 80; ++i4314) {
            r214[i4314] = r212[i4314] == 0 ? r201[i4314] : (r198[i4314]);
        }
        memcpy(r196, r199, sizeof(int32_t) * 1);
        memcpy(r197, r213, sizeof(int32_t) * 80);
        memcpy(r198, r214, sizeof(int32_t) * 80);
    }
    memcpy(r215, r196, sizeof(int32_t) * 1);
    memcpy(r216, r197, sizeof(int32_t) * 80);
    memcpy(r217, r198, sizeof(int32_t) * 80);
    /* sub [sub] -> r218 */
    for (long i4315 = 0; i4315 < 80; ++i4315) {
        r218[i4315] = sub32(r190[i4315], r217[i4315]);
    }
    /* shra [shift_right_arithmetic] -> r219 */
    for (long i4316 = 0; i4316 < 80; ++i4316) {
        r219[i4316] = asr32(r218[i4316], 1);
    }
    /* convert [convert_element_type] -> r222 */
    for (long i4317 = 0; i4317 < 1; ++i4317) {
        r222[i4317] = (int32_t)r220[0];
    }
    /* max [max] -> r223 */
    for (long i4318 = 0; i4318 < 80; ++i4318) {
        r223[i4318] = max32(r222[0], r219[i4318]);
    }
    /* convert [convert_element_type] -> r224 */
    for (long i4319 = 0; i4319 < 1; ++i4319) {
        r224[i4319] = (int32_t)r221[0];
    }
    /* min [min] -> r225 */
    for (long i4320 = 0; i4320 < 80; ++i4320) {
        r225[i4320] = min32(r224[0], r223[i4320]);
    }
    /* sub [sub] -> r226 */
    for (long i4321 = 0; i4321 < 1; ++i4321) {
        r226[i4321] = sub32(r17[i4321], r132[i4321]);
    }
    /* add [add] -> r227 */
    for (long i4322 = 0; i4322 < 1; ++i4322) {
        r227[i4322] = add32(r226[i4322], r30[0]);
    }
    /* max [max] -> r228 */
    for (long i4323 = 0; i4323 < 1; ++i4323) {
        r228[i4323] = max32(r227[i4323], r39[0]);
    }
    /* shra [shift_right_arithmetic] -> r229 */
    for (long i4324 = 0; i4324 < 1; ++i4324) {
        r229[i4324] = asr32(r228[i4324], 1);
    }
    /* concat [concatenate] -> r230 */
    for (long i4325 = 0; i4325 < 15; ++i4325) {
        long t4327 = i4325;
        long c43260 = t4327 / 15; t4327 %= 15;
        long c43261 = t4327;
        r230[c43260 * 95 + (c43261 + 0) * 1] = r1[i4325];
    }
    for (long i4328 = 0; i4328 < 80; ++i4328) {
        long t4330 = i4328;
        long c43290 = t4330 / 80; t4330 %= 80;
        long c43291 = t4330;
        r230[c43290 * 95 + (c43291 + 15) * 1] = r225[i4328];
    }
    /* shl [shift_left] -> r231 */
    for (long i4331 = 0; i4331 < 95; ++i4331) {
        r231[i4331] = shl32(r230[i4331], 1);
    }
    /* rev [rev] -> r232 */
    for (long i4332 = 0; i4332 < 80; ++i4332) {
        long t4334 = i4332;
        long c43330 = t4334 / 16; t4334 %= 16;
        long c43331 = t4334;
        r232[i4332] = r18[c43330 * 16 + (16 - 1 - c43331) * 1];
    }
    /* reshape [reshape] -> r233 */
    memcpy(r233, r232, sizeof(int32_t) * 80);
    /* iota [iota] -> r234 */
    for (long i4335 = 0; i4335 < 80; ++i4335) {
        long t4337 = i4335;
        long c43360 = t4337;
        r234[i4335] = (int32_t)c43360;
    }
    /* broadcast [broadcast_in_dim] -> r235 */
    for (long i4338 = 0; i4338 < 80; ++i4338) {
        long t4340 = i4338;
        long c43390 = t4340 / 1; t4340 %= 1;
        long c43391 = t4340;
        r235[i4338] = r234[c43390 * 1];
    }
    /* iota [iota] -> r236 */
    for (long i4341 = 0; i4341 < 16; ++i4341) {
        long t4343 = i4341;
        long c43420 = t4343;
        r236[i4341] = (int32_t)c43420;
    }
    /* broadcast [broadcast_in_dim] -> r237 */
    for (long i4344 = 0; i4344 < 16; ++i4344) {
        long t4346 = i4344;
        long c43450 = t4346 / 16; t4346 %= 16;
        long c43451 = t4346;
        r237[i4344] = r236[c43451 * 1];
    }
    /* add [add] -> r238 */
    for (long i4347 = 0; i4347 < 1280; ++i4347) {
        long t4349 = i4347;
        long c43480 = t4349 / 16; t4349 %= 16;
        long c43481 = t4349;
        r238[i4347] = add32(r235[c43480 * 1], r237[c43481 * 1]);
    }
    /* lt [lt] -> r239 */
    for (long i4350 = 0; i4350 < 1280; ++i4350) {
        r239[i4350] = r238[i4350] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r241 */
    for (long i4351 = 0; i4351 < 1280; ++i4351) {
        r241[i4351] = add32(r238[i4351], r240[0]);
    }
    /* select_n [select_n] -> r242 */
    for (long i4352 = 0; i4352 < 1280; ++i4352) {
        r242[i4352] = r239[i4352] == 0 ? r238[i4352] : (r241[i4352]);
    }
    /* broadcast [broadcast_in_dim] -> r243 */
    for (long i4353 = 0; i4353 < 1280; ++i4353) {
        long t4355 = i4353;
        long c43540 = t4355 / 16; t4355 %= 16;
        long c43541 = t4355 / 1; t4355 %= 1;
        long c43542 = t4355;
        r243[i4353] = r242[c43540 * 16 + c43541 * 1];
    }
    /* gather [gather] -> r244 */
    for (long i4356 = 0; i4356 < 1280; ++i4356) {
        long t4358 = i4356;
        long c43570 = t4358 / 1280; t4358 %= 1280;
        long c43571 = t4358 / 16; t4358 %= 16;
        long c43572 = t4358;
        long row4359 = c43571 * 16 + c43572 * 1;
        long s4360 = clamp_start((long)r243[row4359 + 0], 95, 1);
        r244[i4356] = r231[c43570 * 95 + s4360 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r245 */
    for (long i4361 = 0; i4361 < 1280; ++i4361) {
        long t4363 = i4361;
        long c43620 = t4363 / 1280; t4363 %= 1280;
        long c43621 = t4363 / 1280; t4363 %= 1280;
        long c43622 = t4363 / 16; t4363 %= 16;
        long c43623 = t4363;
        r245[i4361] = r244[c43622 * 16 + c43623 * 1];
    }
    /* add [add] -> r246 */
    for (long i4364 = 0; i4364 < 6400; ++i4364) {
        long t4366 = i4364;
        long c43650 = t4366 / 1280; t4366 %= 1280;
        long c43651 = t4366 / 1280; t4366 %= 1280;
        long c43652 = t4366 / 16; t4366 %= 16;
        long c43653 = t4366;
        r246[i4364] = add32(r233[c43650 * 16 + c43653 * 1], r245[c43652 * 16 + c43653 * 1]);
    }
    /* convert [convert_element_type] -> r247 */
    for (long i4367 = 0; i4367 < 1; ++i4367) {
        r247[i4367] = (int32_t)r48[0];
    }
    /* max [max] -> r248 */
    for (long i4368 = 0; i4368 < 6400; ++i4368) {
        r248[i4368] = max32(r247[0], r246[i4368]);
    }
    /* convert [convert_element_type] -> r249 */
    for (long i4369 = 0; i4369 < 1; ++i4369) {
        r249[i4369] = (int32_t)r49[0];
    }
    /* min [min] -> r250 */
    for (long i4370 = 0; i4370 < 6400; ++i4370) {
        r250[i4370] = min32(r249[0], r248[i4370]);
    }
    /* sub [sub] -> r251 */
    for (long i4371 = 0; i4371 < 6400; ++i4371) {
        long t4373 = i4371;
        long c43720 = t4373 / 1280; t4373 %= 1280;
        long c43721 = t4373 / 1280; t4373 %= 1280;
        long c43722 = t4373 / 16; t4373 %= 16;
        long c43723 = t4373;
        r251[i4371] = sub32(r233[c43720 * 16 + c43723 * 1], r245[c43722 * 16 + c43723 * 1]);
    }
    /* convert [convert_element_type] -> r252 */
    for (long i4374 = 0; i4374 < 1; ++i4374) {
        r252[i4374] = (int32_t)r48[0];
    }
    /* max [max] -> r253 */
    for (long i4375 = 0; i4375 < 6400; ++i4375) {
        r253[i4375] = max32(r252[0], r251[i4375]);
    }
    /* convert [convert_element_type] -> r254 */
    for (long i4376 = 0; i4376 < 1; ++i4376) {
        r254[i4376] = (int32_t)r49[0];
    }
    /* min [min] -> r255 */
    for (long i4377 = 0; i4377 < 6400; ++i4377) {
        r255[i4377] = min32(r254[0], r253[i4377]);
    }
    /* abs [abs] -> r256 */
    for (long i4378 = 0; i4378 < 6400; ++i4378) {
        r256[i4378] = abs32(r250[i4378]);
    }
    /* reduce_max [reduce_max] -> r257 */
    for (long i4379 = 0; i4379 < 400; ++i4379) {
        r257[i4379] = (-2147483647 - 1);
    }
    for (long i4380 = 0; i4380 < 6400; ++i4380) {
        long t4382 = i4380;
        long c43810 = t4382 / 1280; t4382 %= 1280;
        long c43811 = t4382 / 1280; t4382 %= 1280;
        long c43812 = t4382 / 16; t4382 %= 16;
        long c43813 = t4382;
        r257[c43810 * 80 + c43811 * 80 + c43812 * 1] = max32(r257[c43810 * 80 + c43811 * 80 + c43812 * 1], r256[i4380]);
    }
    /* sub [sub] -> r258 */
    for (long i4383 = 0; i4383 < 400; ++i4383) {
        r258[i4383] = sub32(r257[i4383], r61[0]);
    }
    /* loop [scan] -> r280 */
    memcpy(r259, r250, sizeof(int32_t) * 6400);
    memcpy(r260, r61, sizeof(int32_t) * 1);
    memcpy(r261, r39, sizeof(int32_t) * 1);
    memcpy(r262, r258, sizeof(int32_t) * 400);
    memcpy(r263, r257, sizeof(int32_t) * 400);
    for (long t4384 = 0; t4384 < 12; ++t4384) {
        /* add [add] -> r264 */
        for (long i5385 = 0; i5385 < 1; ++i5385) {
            r264[i5385] = add32(r261[0], r30[0]);
        }
        /* add [add] -> r265 */
        for (long i5386 = 0; i5386 < 400; ++i5386) {
            r265[i5386] = add32(r262[i5386], r263[i5386]);
        }
        /* shra [shift_right_arithmetic] -> r266 */
        for (long i5387 = 0; i5387 < 400; ++i5387) {
            r266[i5387] = asr32(r265[i5387], 1);
        }
        /* broadcast [broadcast_in_dim] -> r267 */
        for (long i5388 = 0; i5388 < 400; ++i5388) {
            long t5390 = i5388;
            long c53890 = t5390 / 80; t5390 %= 80;
            long c53891 = t5390 / 80; t5390 %= 80;
            long c53892 = t5390 / 1; t5390 %= 1;
            long c53893 = t5390;
            r267[i5388] = r266[c53890 * 80 + c53892 * 1];
        }
        /* sub [sub] -> r268 */
        for (long i5391 = 0; i5391 < 6400; ++i5391) {
            long t5393 = i5391;
            long c53920 = t5393 / 1280; t5393 %= 1280;
            long c53921 = t5393 / 1280; t5393 %= 1280;
            long c53922 = t5393 / 16; t5393 %= 16;
            long c53923 = t5393;
            r268[i5391] = sub32(r259[c53920 * 1280 + c53922 * 16 + c53923 * 1], r267[c53920 * 80 + c53922 * 1]);
        }
        /* max [max] -> r269 */
        for (long i5394 = 0; i5394 < 6400; ++i5394) {
            r269[i5394] = max32(r268[i5394], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r270 */
        for (long i5395 = 0; i5395 < 400; ++i5395) {
            r270[i5395] = 0;
        }
        for (long i5396 = 0; i5396 < 6400; ++i5396) {
            long t5398 = i5396;
            long c53970 = t5398 / 1280; t5398 %= 1280;
            long c53971 = t5398 / 1280; t5398 %= 1280;
            long c53972 = t5398 / 16; t5398 %= 16;
            long c53973 = t5398;
            r270[c53970 * 80 + c53971 * 80 + c53972 * 1] = add32(r270[c53970 * 80 + c53971 * 80 + c53972 * 1], r269[i5396]);
        }
        /* neg [neg] -> r271 */
        for (long i5399 = 0; i5399 < 6400; ++i5399) {
            r271[i5399] = neg32(r259[i5399]);
        }
        /* broadcast [broadcast_in_dim] -> r272 */
        for (long i5400 = 0; i5400 < 400; ++i5400) {
            long t5402 = i5400;
            long c54010 = t5402 / 80; t5402 %= 80;
            long c54011 = t5402 / 80; t5402 %= 80;
            long c54012 = t5402 / 1; t5402 %= 1;
            long c54013 = t5402;
            r272[i5400] = r266[c54010 * 80 + c54012 * 1];
        }
        /* sub [sub] -> r273 */
        for (long i5403 = 0; i5403 < 6400; ++i5403) {
            long t5405 = i5403;
            long c54040 = t5405 / 1280; t5405 %= 1280;
            long c54041 = t5405 / 1280; t5405 %= 1280;
            long c54042 = t5405 / 16; t5405 %= 16;
            long c54043 = t5405;
            r273[i5403] = sub32(r271[c54040 * 1280 + c54042 * 16 + c54043 * 1], r272[c54040 * 80 + c54042 * 1]);
        }
        /* max [max] -> r274 */
        for (long i5406 = 0; i5406 < 6400; ++i5406) {
            r274[i5406] = max32(r273[i5406], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r275 */
        for (long i5407 = 0; i5407 < 400; ++i5407) {
            r275[i5407] = 0;
        }
        for (long i5408 = 0; i5408 < 6400; ++i5408) {
            long t5410 = i5408;
            long c54090 = t5410 / 1280; t5410 %= 1280;
            long c54091 = t5410 / 1280; t5410 %= 1280;
            long c54092 = t5410 / 16; t5410 %= 16;
            long c54093 = t5410;
            r275[c54090 * 80 + c54091 * 80 + c54092 * 1] = add32(r275[c54090 * 80 + c54091 * 80 + c54092 * 1], r274[i5408]);
        }
        /* add [add] -> r276 */
        for (long i5411 = 0; i5411 < 400; ++i5411) {
            r276[i5411] = add32(r270[i5411], r275[i5411]);
        }
        /* gt [gt] -> r277 */
        for (long i5412 = 0; i5412 < 400; ++i5412) {
            r277[i5412] = r276[i5412] > r260[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r278 */
        for (long i5413 = 0; i5413 < 400; ++i5413) {
            r278[i5413] = r277[i5413] == 0 ? r262[i5413] : (r266[i5413]);
        }
        /* select_n [select_n] -> r279 */
        for (long i5414 = 0; i5414 < 400; ++i5414) {
            r279[i5414] = r277[i5414] == 0 ? r266[i5414] : (r263[i5414]);
        }
        memcpy(r261, r264, sizeof(int32_t) * 1);
        memcpy(r262, r278, sizeof(int32_t) * 400);
        memcpy(r263, r279, sizeof(int32_t) * 400);
    }
    memcpy(r280, r261, sizeof(int32_t) * 1);
    memcpy(r281, r262, sizeof(int32_t) * 400);
    memcpy(r282, r263, sizeof(int32_t) * 400);
    /* abs [abs] -> r283 */
    for (long i5415 = 0; i5415 < 6400; ++i5415) {
        r283[i5415] = abs32(r255[i5415]);
    }
    /* reduce_max [reduce_max] -> r284 */
    for (long i5416 = 0; i5416 < 400; ++i5416) {
        r284[i5416] = (-2147483647 - 1);
    }
    for (long i5417 = 0; i5417 < 6400; ++i5417) {
        long t5419 = i5417;
        long c54180 = t5419 / 1280; t5419 %= 1280;
        long c54181 = t5419 / 1280; t5419 %= 1280;
        long c54182 = t5419 / 16; t5419 %= 16;
        long c54183 = t5419;
        r284[c54180 * 80 + c54181 * 80 + c54182 * 1] = max32(r284[c54180 * 80 + c54181 * 80 + c54182 * 1], r283[i5417]);
    }
    /* sub [sub] -> r285 */
    for (long i5420 = 0; i5420 < 400; ++i5420) {
        r285[i5420] = sub32(r284[i5420], r61[0]);
    }
    /* loop [scan] -> r307 */
    memcpy(r286, r255, sizeof(int32_t) * 6400);
    memcpy(r287, r61, sizeof(int32_t) * 1);
    memcpy(r288, r39, sizeof(int32_t) * 1);
    memcpy(r289, r285, sizeof(int32_t) * 400);
    memcpy(r290, r284, sizeof(int32_t) * 400);
    for (long t5421 = 0; t5421 < 12; ++t5421) {
        /* add [add] -> r291 */
        for (long i6422 = 0; i6422 < 1; ++i6422) {
            r291[i6422] = add32(r288[0], r30[0]);
        }
        /* add [add] -> r292 */
        for (long i6423 = 0; i6423 < 400; ++i6423) {
            r292[i6423] = add32(r289[i6423], r290[i6423]);
        }
        /* shra [shift_right_arithmetic] -> r293 */
        for (long i6424 = 0; i6424 < 400; ++i6424) {
            r293[i6424] = asr32(r292[i6424], 1);
        }
        /* broadcast [broadcast_in_dim] -> r294 */
        for (long i6425 = 0; i6425 < 400; ++i6425) {
            long t6427 = i6425;
            long c64260 = t6427 / 80; t6427 %= 80;
            long c64261 = t6427 / 80; t6427 %= 80;
            long c64262 = t6427 / 1; t6427 %= 1;
            long c64263 = t6427;
            r294[i6425] = r293[c64260 * 80 + c64262 * 1];
        }
        /* sub [sub] -> r295 */
        for (long i6428 = 0; i6428 < 6400; ++i6428) {
            long t6430 = i6428;
            long c64290 = t6430 / 1280; t6430 %= 1280;
            long c64291 = t6430 / 1280; t6430 %= 1280;
            long c64292 = t6430 / 16; t6430 %= 16;
            long c64293 = t6430;
            r295[i6428] = sub32(r286[c64290 * 1280 + c64292 * 16 + c64293 * 1], r294[c64290 * 80 + c64292 * 1]);
        }
        /* max [max] -> r296 */
        for (long i6431 = 0; i6431 < 6400; ++i6431) {
            r296[i6431] = max32(r295[i6431], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r297 */
        for (long i6432 = 0; i6432 < 400; ++i6432) {
            r297[i6432] = 0;
        }
        for (long i6433 = 0; i6433 < 6400; ++i6433) {
            long t6435 = i6433;
            long c64340 = t6435 / 1280; t6435 %= 1280;
            long c64341 = t6435 / 1280; t6435 %= 1280;
            long c64342 = t6435 / 16; t6435 %= 16;
            long c64343 = t6435;
            r297[c64340 * 80 + c64341 * 80 + c64342 * 1] = add32(r297[c64340 * 80 + c64341 * 80 + c64342 * 1], r296[i6433]);
        }
        /* neg [neg] -> r298 */
        for (long i6436 = 0; i6436 < 6400; ++i6436) {
            r298[i6436] = neg32(r286[i6436]);
        }
        /* broadcast [broadcast_in_dim] -> r299 */
        for (long i6437 = 0; i6437 < 400; ++i6437) {
            long t6439 = i6437;
            long c64380 = t6439 / 80; t6439 %= 80;
            long c64381 = t6439 / 80; t6439 %= 80;
            long c64382 = t6439 / 1; t6439 %= 1;
            long c64383 = t6439;
            r299[i6437] = r293[c64380 * 80 + c64382 * 1];
        }
        /* sub [sub] -> r300 */
        for (long i6440 = 0; i6440 < 6400; ++i6440) {
            long t6442 = i6440;
            long c64410 = t6442 / 1280; t6442 %= 1280;
            long c64411 = t6442 / 1280; t6442 %= 1280;
            long c64412 = t6442 / 16; t6442 %= 16;
            long c64413 = t6442;
            r300[i6440] = sub32(r298[c64410 * 1280 + c64412 * 16 + c64413 * 1], r299[c64410 * 80 + c64412 * 1]);
        }
        /* max [max] -> r301 */
        for (long i6443 = 0; i6443 < 6400; ++i6443) {
            r301[i6443] = max32(r300[i6443], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r302 */
        for (long i6444 = 0; i6444 < 400; ++i6444) {
            r302[i6444] = 0;
        }
        for (long i6445 = 0; i6445 < 6400; ++i6445) {
            long t6447 = i6445;
            long c64460 = t6447 / 1280; t6447 %= 1280;
            long c64461 = t6447 / 1280; t6447 %= 1280;
            long c64462 = t6447 / 16; t6447 %= 16;
            long c64463 = t6447;
            r302[c64460 * 80 + c64461 * 80 + c64462 * 1] = add32(r302[c64460 * 80 + c64461 * 80 + c64462 * 1], r301[i6445]);
        }
        /* add [add] -> r303 */
        for (long i6448 = 0; i6448 < 400; ++i6448) {
            r303[i6448] = add32(r297[i6448], r302[i6448]);
        }
        /* gt [gt] -> r304 */
        for (long i6449 = 0; i6449 < 400; ++i6449) {
            r304[i6449] = r303[i6449] > r287[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r305 */
        for (long i6450 = 0; i6450 < 400; ++i6450) {
            r305[i6450] = r304[i6450] == 0 ? r289[i6450] : (r293[i6450]);
        }
        /* select_n [select_n] -> r306 */
        for (long i6451 = 0; i6451 < 400; ++i6451) {
            r306[i6451] = r304[i6451] == 0 ? r293[i6451] : (r290[i6451]);
        }
        memcpy(r288, r291, sizeof(int32_t) * 1);
        memcpy(r289, r305, sizeof(int32_t) * 400);
        memcpy(r290, r306, sizeof(int32_t) * 400);
    }
    memcpy(r307, r288, sizeof(int32_t) * 1);
    memcpy(r308, r289, sizeof(int32_t) * 400);
    memcpy(r309, r290, sizeof(int32_t) * 400);
    /* sub [sub] -> r310 */
    for (long i6452 = 0; i6452 < 400; ++i6452) {
        r310[i6452] = sub32(r282[i6452], r309[i6452]);
    }
    /* transpose [transpose] -> r311 */
    for (long i6453 = 0; i6453 < 400; ++i6453) {
        long t6455 = i6453;
        long c64540 = t6455 / 400; t6455 %= 400;
        long c64541 = t6455 / 80; t6455 %= 80;
        long c64542 = t6455;
        r311[i6453] = r310[c64540 * 80 + c64541 * 80 + c64542 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r312 */
    for (long i6456 = 0; i6456 < 1; ++i6456) {
        long t6458 = i6456;
        long c64570 = t6458 / 1; t6458 %= 1;
        long c64571 = t6458;
        r312[i6456] = r229[0];
    }
    /* max [max] -> r313 */
    for (long i6459 = 0; i6459 < 400; ++i6459) {
        r313[i6459] = max32(r311[i6459], r39[0]);
    }
    /* iota [iota] -> r314 */
    for (long i6460 = 0; i6460 < 400; ++i6460) {
        long t6462 = i6460;
        long c64610 = t6462 / 400; t6462 %= 400;
        long c64611 = t6462 / 80; t6462 %= 80;
        long c64612 = t6462;
        r314[i6460] = (int32_t)c64612;
    }
    /* broadcast [broadcast_in_dim] -> r315 */
    for (long i6463 = 0; i6463 < 1; ++i6463) {
        long t6465 = i6463;
        long c64640 = t6465 / 1; t6465 %= 1;
        long c64641 = t6465 / 1; t6465 %= 1;
        long c64642 = t6465;
        r315[i6463] = r312[0];
    }
    /* lt [lt] -> r316 */
    for (long i6466 = 0; i6466 < 400; ++i6466) {
        long t6468 = i6466;
        long c64670 = t6468 / 400; t6468 %= 400;
        long c64671 = t6468 / 80; t6468 %= 80;
        long c64672 = t6468;
        r316[i6466] = r314[c64671 * 80 + c64672 * 1] < r315[0] ? 1 : 0;
    }
    /* convert [convert_element_type] -> r317 */
    for (long i6469 = 0; i6469 < 1; ++i6469) {
        r317[i6469] = (int32_t)r39[0];
    }
    /* broadcast [broadcast_in_dim] -> r318 */
    for (long i6470 = 0; i6470 < 400; ++i6470) {
        long t6472 = i6470;
        long c64710 = t6472 / 400; t6472 %= 400;
        long c64711 = t6472 / 80; t6472 %= 80;
        long c64712 = t6472;
        r318[i6470] = r317[0];
    }
    /* select_n [select_n] -> r319 */
    for (long i6473 = 0; i6473 < 400; ++i6473) {
        r319[i6473] = r316[i6473] == 0 ? r318[i6473] : (r313[i6473]);
    }
    /* reduce_sum [reduce_sum] -> r320 */
    for (long i6474 = 0; i6474 < 5; ++i6474) {
        r320[i6474] = 0;
    }
    for (long i6475 = 0; i6475 < 400; ++i6475) {
        long t6477 = i6475;
        long c64760 = t6477 / 400; t6477 %= 400;
        long c64761 = t6477 / 80; t6477 %= 80;
        long c64762 = t6477;
        r320[c64760 * 5 + c64761 * 1] = add32(r320[c64760 * 5 + c64761 * 1], r319[i6475]);
    }
    /* shl [shift_left] -> r321 */
    for (long i6478 = 0; i6478 < 5; ++i6478) {
        r321[i6478] = shl32(r320[i6478], 1);
    }
    /* lt [lt] -> r322 */
    for (long i6479 = 0; i6479 < 1; ++i6479) {
        r322[i6479] = r229[i6479] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r323 */
    for (long i6480 = 0; i6480 < 1; ++i6480) {
        r323[i6480] = add32(r229[i6480], r240[0]);
    }
    /* select_n [select_n] -> r324 */
    for (long i6481 = 0; i6481 < 1; ++i6481) {
        r324[i6481] = r322[i6481] == 0 ? r229[i6481] : (r323[i6481]);
    }
    /* broadcast [broadcast_in_dim] -> r325 */
    for (long i6482 = 0; i6482 < 1; ++i6482) {
        long t6484 = i6482;
        long c64830 = t6484 / 1; t6484 %= 1;
        long c64831 = t6484;
        r325[i6482] = r324[0];
    }
    /* gather [gather] -> r326 */
    for (long i6485 = 0; i6485 < 15; ++i6485) {
        long t6487 = i6485;
        long c64860 = t6487 / 15; t6487 %= 15;
        long c64861 = t6487;
        long row6488 = c64860 * 1;
        long s6489 = clamp_start((long)r325[row6488 + 0], 95, 15);
        r326[i6485] = r230[c64860 * 95 + (s6489 + c64861) * 1];
    }
    /* add [add] -> r327 */
    for (long i6490 = 0; i6490 < 1; ++i6490) {
        r327[i6490] = add32(r7[i6490], r229[i6490]);
    }
    /* and [and] -> r328 */
    for (long i6491 = 0; i6491 < 1; ++i6491) {
        r328[i6491] = r7[i6491] & r30[0];
    }
    /* slice [slice] -> r329 */
    for (long i6492 = 0; i6492 < 85; ++i6492) {
        long t6494 = i6492;
        long c64930 = t6494 / 85; t6494 %= 85;
        long c64931 = t6494;
        r329[i6492] = r230[(0 + c64930 * 1) * 95 + (10 + c64931 * 1) * 1];
    }
    /* shl [shift_left] -> r330 */
    for (long i6495 = 0; i6495 < 85; ++i6495) {
        r330[i6495] = shl32(r329[i6495], 1);
    }
    /* convert [convert_element_type] -> r331 */
    for (long i6496 = 0; i6496 < 1; ++i6496) {
        r331[i6496] = (int32_t)r39[0];
    }
    /* pad [pad] -> r332 */
    for (long i6497 = 0; i6497 < 86; ++i6497) {
        r332[i6497] = r331[0];
    }
    for (long i6498 = 0; i6498 < 85; ++i6498) {
        long t6500 = i6498;
        long c64990 = t6500 / 85; t6500 %= 85;
        long c64991 = t6500;
        long d6501 = 0 + c64990 * 1;
        long d6502 = 0 + c64991 * 1;
        if (d6501 >= 0 && d6501 < 1 && d6502 >= 0 && d6502 < 86) r332[d6501 * 86 + d6502 * 1] = r330[i6498];
    }
    /* iota [iota] -> r333 */
    for (long i6503 = 0; i6503 < 40; ++i6503) {
        long t6505 = i6503;
        long c65040 = t6505;
        r333[i6503] = (int32_t)c65040;
    }
    /* shl [shift_left] -> r334 */
    for (long i6506 = 0; i6506 < 40; ++i6506) {
        r334[i6506] = shl32(r333[i6506], 1);
    }
    /* broadcast [broadcast_in_dim] -> r335 */
    for (long i6507 = 0; i6507 < 40; ++i6507) {
        long t6509 = i6507;
        long c65080 = t6509 / 1; t6509 %= 1;
        long c65081 = t6509;
        r335[i6507] = r334[c65080 * 1];
    }
    /* iota [iota] -> r336 */
    for (long i6510 = 0; i6510 < 6; ++i6510) {
        long t6512 = i6510;
        long c65110 = t6512;
        r336[i6510] = (int32_t)c65110;
    }
    /* broadcast [broadcast_in_dim] -> r337 */
    for (long i6513 = 0; i6513 < 6; ++i6513) {
        long t6515 = i6513;
        long c65140 = t6515 / 6; t6515 %= 6;
        long c65141 = t6515;
        r337[i6513] = r336[c65141 * 1];
    }
    /* add [add] -> r338 */
    for (long i6516 = 0; i6516 < 240; ++i6516) {
        long t6518 = i6516;
        long c65170 = t6518 / 6; t6518 %= 6;
        long c65171 = t6518;
        r338[i6516] = add32(r335[c65170 * 1], r337[c65171 * 1]);
    }
    /* broadcast [broadcast_in_dim] -> r339 */
    for (long i6519 = 0; i6519 < 240; ++i6519) {
        long t6521 = i6519;
        long c65200 = t6521 / 240; t6521 %= 240;
        long c65201 = t6521 / 6; t6521 %= 6;
        long c65202 = t6521;
        r339[i6519] = r338[c65201 * 6 + c65202 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r340 */
    for (long i6522 = 0; i6522 < 1; ++i6522) {
        long t6524 = i6522;
        long c65230 = t6524 / 1; t6524 %= 1;
        long c65231 = t6524 / 1; t6524 %= 1;
        long c65232 = t6524;
        r340[i6522] = r328[0];
    }
    /* add [add] -> r341 */
    for (long i6525 = 0; i6525 < 240; ++i6525) {
        long t6527 = i6525;
        long c65260 = t6527 / 240; t6527 %= 240;
        long c65261 = t6527 / 6; t6527 %= 6;
        long c65262 = t6527;
        r341[i6525] = add32(r340[0], r339[c65261 * 6 + c65262 * 1]);
    }
    /* lt [lt] -> r342 */
    for (long i6528 = 0; i6528 < 240; ++i6528) {
        r342[i6528] = r341[i6528] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r344 */
    for (long i6529 = 0; i6529 < 240; ++i6529) {
        r344[i6529] = add32(r341[i6529], r343[0]);
    }
    /* select_n [select_n] -> r345 */
    for (long i6530 = 0; i6530 < 240; ++i6530) {
        r345[i6530] = r342[i6530] == 0 ? r341[i6530] : (r344[i6530]);
    }
    /* broadcast [broadcast_in_dim] -> r346 */
    for (long i6531 = 0; i6531 < 240; ++i6531) {
        long t6533 = i6531;
        long c65320 = t6533 / 240; t6533 %= 240;
        long c65321 = t6533 / 6; t6533 %= 6;
        long c65322 = t6533 / 1; t6533 %= 1;
        long c65323 = t6533;
        r346[i6531] = r345[c65321 * 6 + c65322 * 1];
    }
    /* gather [gather] -> r347 */
    for (long i6534 = 0; i6534 < 240; ++i6534) {
        long t6536 = i6534;
        long c65350 = t6536 / 240; t6536 %= 240;
        long c65351 = t6536 / 6; t6536 %= 6;
        long c65352 = t6536;
        long row6537 = c65350 * 240 + c65351 * 6 + c65352 * 1;
        long s6538 = clamp_start((long)r346[row6537 + 0], 86, 1);
        r347[i6534] = r332[c65350 * 86 + s6538 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r348 */
    for (long i6539 = 0; i6539 < 6; ++i6539) {
        long t6541 = i6539;
        long c65400 = t6541 / 6; t6541 %= 6;
        long c65401 = t6541 / 6; t6541 %= 6;
        long c65402 = t6541;
        r348[i6539] = r19[c65402 * 1];
    }
    /* add [add] -> r349 */
    for (long i6542 = 0; i6542 < 240; ++i6542) {
        long t6544 = i6542;
        long c65430 = t6544 / 240; t6544 %= 240;
        long c65431 = t6544 / 6; t6544 %= 6;
        long c65432 = t6544;
        r349[i6542] = add32(r348[c65432 * 1], r347[c65431 * 6 + c65432 * 1]);
    }
    /* convert [convert_element_type] -> r350 */
    for (long i6545 = 0; i6545 < 1; ++i6545) {
        r350[i6545] = (int32_t)r48[0];
    }
    /* max [max] -> r351 */
    for (long i6546 = 0; i6546 < 240; ++i6546) {
        r351[i6546] = max32(r350[0], r349[i6546]);
    }
    /* convert [convert_element_type] -> r352 */
    for (long i6547 = 0; i6547 < 1; ++i6547) {
        r352[i6547] = (int32_t)r49[0];
    }
    /* min [min] -> r353 */
    for (long i6548 = 0; i6548 < 240; ++i6548) {
        r353[i6548] = min32(r352[0], r351[i6548]);
    }
    /* broadcast [broadcast_in_dim] -> r354 */
    for (long i6549 = 0; i6549 < 6; ++i6549) {
        long t6551 = i6549;
        long c65500 = t6551 / 6; t6551 %= 6;
        long c65501 = t6551 / 6; t6551 %= 6;
        long c65502 = t6551;
        r354[i6549] = r19[c65502 * 1];
    }
    /* sub [sub] -> r355 */
    for (long i6552 = 0; i6552 < 240; ++i6552) {
        long t6554 = i6552;
        long c65530 = t6554 / 240; t6554 %= 240;
        long c65531 = t6554 / 6; t6554 %= 6;
        long c65532 = t6554;
        r355[i6552] = sub32(r354[c65532 * 1], r347[c65531 * 6 + c65532 * 1]);
    }
    /* convert [convert_element_type] -> r356 */
    for (long i6555 = 0; i6555 < 1; ++i6555) {
        r356[i6555] = (int32_t)r48[0];
    }
    /* max [max] -> r357 */
    for (long i6556 = 0; i6556 < 240; ++i6556) {
        r357[i6556] = max32(r356[0], r355[i6556]);
    }
    /* convert [convert_element_type] -> r358 */
    for (long i6557 = 0; i6557 < 1; ++i6557) {
        r358[i6557] = (int32_t)r49[0];
    }
    /* min [min] -> r359 */
    for (long i6558 = 0; i6558 < 240; ++i6558) {
        r359[i6558] = min32(r358[0], r357[i6558]);
    }
    /* abs [abs] -> r360 */
    for (long i6559 = 0; i6559 < 240; ++i6559) {
        r360[i6559] = abs32(r353[i6559]);
    }
    /* reduce_max [reduce_max] -> r361 */
    for (long i6560 = 0; i6560 < 40; ++i6560) {
        r361[i6560] = (-2147483647 - 1);
    }
    for (long i6561 = 0; i6561 < 240; ++i6561) {
        long t6563 = i6561;
        long c65620 = t6563 / 240; t6563 %= 240;
        long c65621 = t6563 / 6; t6563 %= 6;
        long c65622 = t6563;
        r361[c65620 * 40 + c65621 * 1] = max32(r361[c65620 * 40 + c65621 * 1], r360[i6561]);
    }
    /* sub [sub] -> r362 */
    for (long i6564 = 0; i6564 < 40; ++i6564) {
        r362[i6564] = sub32(r361[i6564], r61[0]);
    }
    /* loop [scan] -> r384 */
    memcpy(r363, r353, sizeof(int32_t) * 240);
    memcpy(r364, r61, sizeof(int32_t) * 1);
    memcpy(r365, r39, sizeof(int32_t) * 1);
    memcpy(r366, r362, sizeof(int32_t) * 40);
    memcpy(r367, r361, sizeof(int32_t) * 40);
    for (long t6565 = 0; t6565 < 12; ++t6565) {
        /* add [add] -> r368 */
        for (long i7566 = 0; i7566 < 1; ++i7566) {
            r368[i7566] = add32(r365[0], r30[0]);
        }
        /* add [add] -> r369 */
        for (long i7567 = 0; i7567 < 40; ++i7567) {
            r369[i7567] = add32(r366[i7567], r367[i7567]);
        }
        /* shra [shift_right_arithmetic] -> r370 */
        for (long i7568 = 0; i7568 < 40; ++i7568) {
            r370[i7568] = asr32(r369[i7568], 1);
        }
        /* broadcast [broadcast_in_dim] -> r371 */
        for (long i7569 = 0; i7569 < 40; ++i7569) {
            long t7571 = i7569;
            long c75700 = t7571 / 40; t7571 %= 40;
            long c75701 = t7571 / 1; t7571 %= 1;
            long c75702 = t7571;
            r371[i7569] = r370[c75701 * 1];
        }
        /* sub [sub] -> r372 */
        for (long i7572 = 0; i7572 < 240; ++i7572) {
            long t7574 = i7572;
            long c75730 = t7574 / 240; t7574 %= 240;
            long c75731 = t7574 / 6; t7574 %= 6;
            long c75732 = t7574;
            r372[i7572] = sub32(r363[c75731 * 6 + c75732 * 1], r371[c75731 * 1]);
        }
        /* max [max] -> r373 */
        for (long i7575 = 0; i7575 < 240; ++i7575) {
            r373[i7575] = max32(r372[i7575], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r374 */
        for (long i7576 = 0; i7576 < 40; ++i7576) {
            r374[i7576] = 0;
        }
        for (long i7577 = 0; i7577 < 240; ++i7577) {
            long t7579 = i7577;
            long c75780 = t7579 / 240; t7579 %= 240;
            long c75781 = t7579 / 6; t7579 %= 6;
            long c75782 = t7579;
            r374[c75780 * 40 + c75781 * 1] = add32(r374[c75780 * 40 + c75781 * 1], r373[i7577]);
        }
        /* neg [neg] -> r375 */
        for (long i7580 = 0; i7580 < 240; ++i7580) {
            r375[i7580] = neg32(r363[i7580]);
        }
        /* broadcast [broadcast_in_dim] -> r376 */
        for (long i7581 = 0; i7581 < 40; ++i7581) {
            long t7583 = i7581;
            long c75820 = t7583 / 40; t7583 %= 40;
            long c75821 = t7583 / 1; t7583 %= 1;
            long c75822 = t7583;
            r376[i7581] = r370[c75821 * 1];
        }
        /* sub [sub] -> r377 */
        for (long i7584 = 0; i7584 < 240; ++i7584) {
            long t7586 = i7584;
            long c75850 = t7586 / 240; t7586 %= 240;
            long c75851 = t7586 / 6; t7586 %= 6;
            long c75852 = t7586;
            r377[i7584] = sub32(r375[c75851 * 6 + c75852 * 1], r376[c75851 * 1]);
        }
        /* max [max] -> r378 */
        for (long i7587 = 0; i7587 < 240; ++i7587) {
            r378[i7587] = max32(r377[i7587], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r379 */
        for (long i7588 = 0; i7588 < 40; ++i7588) {
            r379[i7588] = 0;
        }
        for (long i7589 = 0; i7589 < 240; ++i7589) {
            long t7591 = i7589;
            long c75900 = t7591 / 240; t7591 %= 240;
            long c75901 = t7591 / 6; t7591 %= 6;
            long c75902 = t7591;
            r379[c75900 * 40 + c75901 * 1] = add32(r379[c75900 * 40 + c75901 * 1], r378[i7589]);
        }
        /* add [add] -> r380 */
        for (long i7592 = 0; i7592 < 40; ++i7592) {
            r380[i7592] = add32(r374[i7592], r379[i7592]);
        }
        /* gt [gt] -> r381 */
        for (long i7593 = 0; i7593 < 40; ++i7593) {
            r381[i7593] = r380[i7593] > r364[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r382 */
        for (long i7594 = 0; i7594 < 40; ++i7594) {
            r382[i7594] = r381[i7594] == 0 ? r366[i7594] : (r370[i7594]);
        }
        /* select_n [select_n] -> r383 */
        for (long i7595 = 0; i7595 < 40; ++i7595) {
            r383[i7595] = r381[i7595] == 0 ? r370[i7595] : (r367[i7595]);
        }
        memcpy(r365, r368, sizeof(int32_t) * 1);
        memcpy(r366, r382, sizeof(int32_t) * 40);
        memcpy(r367, r383, sizeof(int32_t) * 40);
    }
    memcpy(r384, r365, sizeof(int32_t) * 1);
    memcpy(r385, r366, sizeof(int32_t) * 40);
    memcpy(r386, r367, sizeof(int32_t) * 40);
    /* abs [abs] -> r387 */
    for (long i7596 = 0; i7596 < 240; ++i7596) {
        r387[i7596] = abs32(r359[i7596]);
    }
    /* reduce_max [reduce_max] -> r388 */
    for (long i7597 = 0; i7597 < 40; ++i7597) {
        r388[i7597] = (-2147483647 - 1);
    }
    for (long i7598 = 0; i7598 < 240; ++i7598) {
        long t7600 = i7598;
        long c75990 = t7600 / 240; t7600 %= 240;
        long c75991 = t7600 / 6; t7600 %= 6;
        long c75992 = t7600;
        r388[c75990 * 40 + c75991 * 1] = max32(r388[c75990 * 40 + c75991 * 1], r387[i7598]);
    }
    /* sub [sub] -> r389 */
    for (long i7601 = 0; i7601 < 40; ++i7601) {
        r389[i7601] = sub32(r388[i7601], r61[0]);
    }
    /* loop [scan] -> r411 */
    memcpy(r390, r359, sizeof(int32_t) * 240);
    memcpy(r391, r61, sizeof(int32_t) * 1);
    memcpy(r392, r39, sizeof(int32_t) * 1);
    memcpy(r393, r389, sizeof(int32_t) * 40);
    memcpy(r394, r388, sizeof(int32_t) * 40);
    for (long t7602 = 0; t7602 < 12; ++t7602) {
        /* add [add] -> r395 */
        for (long i8603 = 0; i8603 < 1; ++i8603) {
            r395[i8603] = add32(r392[0], r30[0]);
        }
        /* add [add] -> r396 */
        for (long i8604 = 0; i8604 < 40; ++i8604) {
            r396[i8604] = add32(r393[i8604], r394[i8604]);
        }
        /* shra [shift_right_arithmetic] -> r397 */
        for (long i8605 = 0; i8605 < 40; ++i8605) {
            r397[i8605] = asr32(r396[i8605], 1);
        }
        /* broadcast [broadcast_in_dim] -> r398 */
        for (long i8606 = 0; i8606 < 40; ++i8606) {
            long t8608 = i8606;
            long c86070 = t8608 / 40; t8608 %= 40;
            long c86071 = t8608 / 1; t8608 %= 1;
            long c86072 = t8608;
            r398[i8606] = r397[c86071 * 1];
        }
        /* sub [sub] -> r399 */
        for (long i8609 = 0; i8609 < 240; ++i8609) {
            long t8611 = i8609;
            long c86100 = t8611 / 240; t8611 %= 240;
            long c86101 = t8611 / 6; t8611 %= 6;
            long c86102 = t8611;
            r399[i8609] = sub32(r390[c86101 * 6 + c86102 * 1], r398[c86101 * 1]);
        }
        /* max [max] -> r400 */
        for (long i8612 = 0; i8612 < 240; ++i8612) {
            r400[i8612] = max32(r399[i8612], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r401 */
        for (long i8613 = 0; i8613 < 40; ++i8613) {
            r401[i8613] = 0;
        }
        for (long i8614 = 0; i8614 < 240; ++i8614) {
            long t8616 = i8614;
            long c86150 = t8616 / 240; t8616 %= 240;
            long c86151 = t8616 / 6; t8616 %= 6;
            long c86152 = t8616;
            r401[c86150 * 40 + c86151 * 1] = add32(r401[c86150 * 40 + c86151 * 1], r400[i8614]);
        }
        /* neg [neg] -> r402 */
        for (long i8617 = 0; i8617 < 240; ++i8617) {
            r402[i8617] = neg32(r390[i8617]);
        }
        /* broadcast [broadcast_in_dim] -> r403 */
        for (long i8618 = 0; i8618 < 40; ++i8618) {
            long t8620 = i8618;
            long c86190 = t8620 / 40; t8620 %= 40;
            long c86191 = t8620 / 1; t8620 %= 1;
            long c86192 = t8620;
            r403[i8618] = r397[c86191 * 1];
        }
        /* sub [sub] -> r404 */
        for (long i8621 = 0; i8621 < 240; ++i8621) {
            long t8623 = i8621;
            long c86220 = t8623 / 240; t8623 %= 240;
            long c86221 = t8623 / 6; t8623 %= 6;
            long c86222 = t8623;
            r404[i8621] = sub32(r402[c86221 * 6 + c86222 * 1], r403[c86221 * 1]);
        }
        /* max [max] -> r405 */
        for (long i8624 = 0; i8624 < 240; ++i8624) {
            r405[i8624] = max32(r404[i8624], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r406 */
        for (long i8625 = 0; i8625 < 40; ++i8625) {
            r406[i8625] = 0;
        }
        for (long i8626 = 0; i8626 < 240; ++i8626) {
            long t8628 = i8626;
            long c86270 = t8628 / 240; t8628 %= 240;
            long c86271 = t8628 / 6; t8628 %= 6;
            long c86272 = t8628;
            r406[c86270 * 40 + c86271 * 1] = add32(r406[c86270 * 40 + c86271 * 1], r405[i8626]);
        }
        /* add [add] -> r407 */
        for (long i8629 = 0; i8629 < 40; ++i8629) {
            r407[i8629] = add32(r401[i8629], r406[i8629]);
        }
        /* gt [gt] -> r408 */
        for (long i8630 = 0; i8630 < 40; ++i8630) {
            r408[i8630] = r407[i8630] > r391[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r409 */
        for (long i8631 = 0; i8631 < 40; ++i8631) {
            r409[i8631] = r408[i8631] == 0 ? r393[i8631] : (r397[i8631]);
        }
        /* select_n [select_n] -> r410 */
        for (long i8632 = 0; i8632 < 40; ++i8632) {
            r410[i8632] = r408[i8632] == 0 ? r397[i8632] : (r394[i8632]);
        }
        memcpy(r392, r395, sizeof(int32_t) * 1);
        memcpy(r393, r409, sizeof(int32_t) * 40);
        memcpy(r394, r410, sizeof(int32_t) * 40);
    }
    memcpy(r411, r392, sizeof(int32_t) * 1);
    memcpy(r412, r393, sizeof(int32_t) * 40);
    memcpy(r413, r394, sizeof(int32_t) * 40);
    /* sub [sub] -> r414 */
    for (long i8633 = 0; i8633 < 40; ++i8633) {
        r414[i8633] = sub32(r386[i8633], r413[i8633]);
    }
    /* shra [shift_right_arithmetic] -> r415 */
    for (long i8634 = 0; i8634 < 40; ++i8634) {
        r415[i8634] = asr32(r414[i8634], 1);
    }
    /* convert [convert_element_type] -> r416 */
    for (long i8635 = 0; i8635 < 1; ++i8635) {
        r416[i8635] = (int32_t)r220[0];
    }
    /* max [max] -> r417 */
    for (long i8636 = 0; i8636 < 40; ++i8636) {
        r417[i8636] = max32(r416[0], r415[i8636]);
    }
    /* convert [convert_element_type] -> r418 */
    for (long i8637 = 0; i8637 < 1; ++i8637) {
        r418[i8637] = (int32_t)r221[0];
    }
    /* min [min] -> r419 */
    for (long i8638 = 0; i8638 < 40; ++i8638) {
        r419[i8638] = min32(r418[0], r417[i8638]);
    }
    /* sub [sub] -> r420 */
    for (long i8639 = 0; i8639 < 1; ++i8639) {
        r420[i8639] = sub32(r229[i8639], r328[i8639]);
    }
    /* add [add] -> r421 */
    for (long i8640 = 0; i8640 < 1; ++i8640) {
        r421[i8640] = add32(r420[i8640], r30[0]);
    }
    /* max [max] -> r422 */
    for (long i8641 = 0; i8641 < 1; ++i8641) {
        r422[i8641] = max32(r421[i8641], r39[0]);
    }
    /* shra [shift_right_arithmetic] -> r423 */
    for (long i8642 = 0; i8642 < 1; ++i8642) {
        r423[i8642] = asr32(r422[i8642], 1);
    }
    /* concat [concatenate] -> r424 */
    for (long i8643 = 0; i8643 < 15; ++i8643) {
        long t8645 = i8643;
        long c86440 = t8645 / 15; t8645 %= 15;
        long c86441 = t8645;
        r424[c86440 * 55 + (c86441 + 0) * 1] = r2[i8643];
    }
    for (long i8646 = 0; i8646 < 40; ++i8646) {
        long t8648 = i8646;
        long c86470 = t8648 / 40; t8648 %= 40;
        long c86471 = t8648;
        r424[c86470 * 55 + (c86471 + 15) * 1] = r419[i8646];
    }
    /* shl [shift_left] -> r425 */
    for (long i8649 = 0; i8649 < 55; ++i8649) {
        r425[i8649] = shl32(r424[i8649], 1);
    }
    /* rev [rev] -> r426 */
    for (long i8650 = 0; i8650 < 80; ++i8650) {
        long t8652 = i8650;
        long c86510 = t8652 / 16; t8652 %= 16;
        long c86511 = t8652;
        r426[i8650] = r18[c86510 * 16 + (16 - 1 - c86511) * 1];
    }
    /* reshape [reshape] -> r427 */
    memcpy(r427, r426, sizeof(int32_t) * 80);
    /* iota [iota] -> r428 */
    for (long i8653 = 0; i8653 < 40; ++i8653) {
        long t8655 = i8653;
        long c86540 = t8655;
        r428[i8653] = (int32_t)c86540;
    }
    /* broadcast [broadcast_in_dim] -> r429 */
    for (long i8656 = 0; i8656 < 40; ++i8656) {
        long t8658 = i8656;
        long c86570 = t8658 / 1; t8658 %= 1;
        long c86571 = t8658;
        r429[i8656] = r428[c86570 * 1];
    }
    /* iota [iota] -> r430 */
    for (long i8659 = 0; i8659 < 16; ++i8659) {
        long t8661 = i8659;
        long c86600 = t8661;
        r430[i8659] = (int32_t)c86600;
    }
    /* broadcast [broadcast_in_dim] -> r431 */
    for (long i8662 = 0; i8662 < 16; ++i8662) {
        long t8664 = i8662;
        long c86630 = t8664 / 16; t8664 %= 16;
        long c86631 = t8664;
        r431[i8662] = r430[c86631 * 1];
    }
    /* add [add] -> r432 */
    for (long i8665 = 0; i8665 < 640; ++i8665) {
        long t8667 = i8665;
        long c86660 = t8667 / 16; t8667 %= 16;
        long c86661 = t8667;
        r432[i8665] = add32(r429[c86660 * 1], r431[c86661 * 1]);
    }
    /* lt [lt] -> r433 */
    for (long i8668 = 0; i8668 < 640; ++i8668) {
        r433[i8668] = r432[i8668] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r435 */
    for (long i8669 = 0; i8669 < 640; ++i8669) {
        r435[i8669] = add32(r432[i8669], r434[0]);
    }
    /* select_n [select_n] -> r436 */
    for (long i8670 = 0; i8670 < 640; ++i8670) {
        r436[i8670] = r433[i8670] == 0 ? r432[i8670] : (r435[i8670]);
    }
    /* broadcast [broadcast_in_dim] -> r437 */
    for (long i8671 = 0; i8671 < 640; ++i8671) {
        long t8673 = i8671;
        long c86720 = t8673 / 16; t8673 %= 16;
        long c86721 = t8673 / 1; t8673 %= 1;
        long c86722 = t8673;
        r437[i8671] = r436[c86720 * 16 + c86721 * 1];
    }
    /* gather [gather] -> r438 */
    for (long i8674 = 0; i8674 < 640; ++i8674) {
        long t8676 = i8674;
        long c86750 = t8676 / 640; t8676 %= 640;
        long c86751 = t8676 / 16; t8676 %= 16;
        long c86752 = t8676;
        long row8677 = c86751 * 16 + c86752 * 1;
        long s8678 = clamp_start((long)r437[row8677 + 0], 55, 1);
        r438[i8674] = r425[c86750 * 55 + s8678 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r439 */
    for (long i8679 = 0; i8679 < 640; ++i8679) {
        long t8681 = i8679;
        long c86800 = t8681 / 640; t8681 %= 640;
        long c86801 = t8681 / 640; t8681 %= 640;
        long c86802 = t8681 / 16; t8681 %= 16;
        long c86803 = t8681;
        r439[i8679] = r438[c86802 * 16 + c86803 * 1];
    }
    /* add [add] -> r440 */
    for (long i8682 = 0; i8682 < 3200; ++i8682) {
        long t8684 = i8682;
        long c86830 = t8684 / 640; t8684 %= 640;
        long c86831 = t8684 / 640; t8684 %= 640;
        long c86832 = t8684 / 16; t8684 %= 16;
        long c86833 = t8684;
        r440[i8682] = add32(r427[c86830 * 16 + c86833 * 1], r439[c86832 * 16 + c86833 * 1]);
    }
    /* convert [convert_element_type] -> r441 */
    for (long i8685 = 0; i8685 < 1; ++i8685) {
        r441[i8685] = (int32_t)r48[0];
    }
    /* max [max] -> r442 */
    for (long i8686 = 0; i8686 < 3200; ++i8686) {
        r442[i8686] = max32(r441[0], r440[i8686]);
    }
    /* convert [convert_element_type] -> r443 */
    for (long i8687 = 0; i8687 < 1; ++i8687) {
        r443[i8687] = (int32_t)r49[0];
    }
    /* min [min] -> r444 */
    for (long i8688 = 0; i8688 < 3200; ++i8688) {
        r444[i8688] = min32(r443[0], r442[i8688]);
    }
    /* sub [sub] -> r445 */
    for (long i8689 = 0; i8689 < 3200; ++i8689) {
        long t8691 = i8689;
        long c86900 = t8691 / 640; t8691 %= 640;
        long c86901 = t8691 / 640; t8691 %= 640;
        long c86902 = t8691 / 16; t8691 %= 16;
        long c86903 = t8691;
        r445[i8689] = sub32(r427[c86900 * 16 + c86903 * 1], r439[c86902 * 16 + c86903 * 1]);
    }
    /* convert [convert_element_type] -> r446 */
    for (long i8692 = 0; i8692 < 1; ++i8692) {
        r446[i8692] = (int32_t)r48[0];
    }
    /* max [max] -> r447 */
    for (long i8693 = 0; i8693 < 3200; ++i8693) {
        r447[i8693] = max32(r446[0], r445[i8693]);
    }
    /* convert [convert_element_type] -> r448 */
    for (long i8694 = 0; i8694 < 1; ++i8694) {
        r448[i8694] = (int32_t)r49[0];
    }
    /* min [min] -> r449 */
    for (long i8695 = 0; i8695 < 3200; ++i8695) {
        r449[i8695] = min32(r448[0], r447[i8695]);
    }
    /* abs [abs] -> r450 */
    for (long i8696 = 0; i8696 < 3200; ++i8696) {
        r450[i8696] = abs32(r444[i8696]);
    }
    /* reduce_max [reduce_max] -> r451 */
    for (long i8697 = 0; i8697 < 200; ++i8697) {
        r451[i8697] = (-2147483647 - 1);
    }
    for (long i8698 = 0; i8698 < 3200; ++i8698) {
        long t8700 = i8698;
        long c86990 = t8700 / 640; t8700 %= 640;
        long c86991 = t8700 / 640; t8700 %= 640;
        long c86992 = t8700 / 16; t8700 %= 16;
        long c86993 = t8700;
        r451[c86990 * 40 + c86991 * 40 + c86992 * 1] = max32(r451[c86990 * 40 + c86991 * 40 + c86992 * 1], r450[i8698]);
    }
    /* sub [sub] -> r452 */
    for (long i8701 = 0; i8701 < 200; ++i8701) {
        r452[i8701] = sub32(r451[i8701], r61[0]);
    }
    /* loop [scan] -> r474 */
    memcpy(r453, r444, sizeof(int32_t) * 3200);
    memcpy(r454, r61, sizeof(int32_t) * 1);
    memcpy(r455, r39, sizeof(int32_t) * 1);
    memcpy(r456, r452, sizeof(int32_t) * 200);
    memcpy(r457, r451, sizeof(int32_t) * 200);
    for (long t8702 = 0; t8702 < 12; ++t8702) {
        /* add [add] -> r458 */
        for (long i9703 = 0; i9703 < 1; ++i9703) {
            r458[i9703] = add32(r455[0], r30[0]);
        }
        /* add [add] -> r459 */
        for (long i9704 = 0; i9704 < 200; ++i9704) {
            r459[i9704] = add32(r456[i9704], r457[i9704]);
        }
        /* shra [shift_right_arithmetic] -> r460 */
        for (long i9705 = 0; i9705 < 200; ++i9705) {
            r460[i9705] = asr32(r459[i9705], 1);
        }
        /* broadcast [broadcast_in_dim] -> r461 */
        for (long i9706 = 0; i9706 < 200; ++i9706) {
            long t9708 = i9706;
            long c97070 = t9708 / 40; t9708 %= 40;
            long c97071 = t9708 / 40; t9708 %= 40;
            long c97072 = t9708 / 1; t9708 %= 1;
            long c97073 = t9708;
            r461[i9706] = r460[c97070 * 40 + c97072 * 1];
        }
        /* sub [sub] -> r462 */
        for (long i9709 = 0; i9709 < 3200; ++i9709) {
            long t9711 = i9709;
            long c97100 = t9711 / 640; t9711 %= 640;
            long c97101 = t9711 / 640; t9711 %= 640;
            long c97102 = t9711 / 16; t9711 %= 16;
            long c97103 = t9711;
            r462[i9709] = sub32(r453[c97100 * 640 + c97102 * 16 + c97103 * 1], r461[c97100 * 40 + c97102 * 1]);
        }
        /* max [max] -> r463 */
        for (long i9712 = 0; i9712 < 3200; ++i9712) {
            r463[i9712] = max32(r462[i9712], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r464 */
        for (long i9713 = 0; i9713 < 200; ++i9713) {
            r464[i9713] = 0;
        }
        for (long i9714 = 0; i9714 < 3200; ++i9714) {
            long t9716 = i9714;
            long c97150 = t9716 / 640; t9716 %= 640;
            long c97151 = t9716 / 640; t9716 %= 640;
            long c97152 = t9716 / 16; t9716 %= 16;
            long c97153 = t9716;
            r464[c97150 * 40 + c97151 * 40 + c97152 * 1] = add32(r464[c97150 * 40 + c97151 * 40 + c97152 * 1], r463[i9714]);
        }
        /* neg [neg] -> r465 */
        for (long i9717 = 0; i9717 < 3200; ++i9717) {
            r465[i9717] = neg32(r453[i9717]);
        }
        /* broadcast [broadcast_in_dim] -> r466 */
        for (long i9718 = 0; i9718 < 200; ++i9718) {
            long t9720 = i9718;
            long c97190 = t9720 / 40; t9720 %= 40;
            long c97191 = t9720 / 40; t9720 %= 40;
            long c97192 = t9720 / 1; t9720 %= 1;
            long c97193 = t9720;
            r466[i9718] = r460[c97190 * 40 + c97192 * 1];
        }
        /* sub [sub] -> r467 */
        for (long i9721 = 0; i9721 < 3200; ++i9721) {
            long t9723 = i9721;
            long c97220 = t9723 / 640; t9723 %= 640;
            long c97221 = t9723 / 640; t9723 %= 640;
            long c97222 = t9723 / 16; t9723 %= 16;
            long c97223 = t9723;
            r467[i9721] = sub32(r465[c97220 * 640 + c97222 * 16 + c97223 * 1], r466[c97220 * 40 + c97222 * 1]);
        }
        /* max [max] -> r468 */
        for (long i9724 = 0; i9724 < 3200; ++i9724) {
            r468[i9724] = max32(r467[i9724], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r469 */
        for (long i9725 = 0; i9725 < 200; ++i9725) {
            r469[i9725] = 0;
        }
        for (long i9726 = 0; i9726 < 3200; ++i9726) {
            long t9728 = i9726;
            long c97270 = t9728 / 640; t9728 %= 640;
            long c97271 = t9728 / 640; t9728 %= 640;
            long c97272 = t9728 / 16; t9728 %= 16;
            long c97273 = t9728;
            r469[c97270 * 40 + c97271 * 40 + c97272 * 1] = add32(r469[c97270 * 40 + c97271 * 40 + c97272 * 1], r468[i9726]);
        }
        /* add [add] -> r470 */
        for (long i9729 = 0; i9729 < 200; ++i9729) {
            r470[i9729] = add32(r464[i9729], r469[i9729]);
        }
        /* gt [gt] -> r471 */
        for (long i9730 = 0; i9730 < 200; ++i9730) {
            r471[i9730] = r470[i9730] > r454[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r472 */
        for (long i9731 = 0; i9731 < 200; ++i9731) {
            r472[i9731] = r471[i9731] == 0 ? r456[i9731] : (r460[i9731]);
        }
        /* select_n [select_n] -> r473 */
        for (long i9732 = 0; i9732 < 200; ++i9732) {
            r473[i9732] = r471[i9732] == 0 ? r460[i9732] : (r457[i9732]);
        }
        memcpy(r455, r458, sizeof(int32_t) * 1);
        memcpy(r456, r472, sizeof(int32_t) * 200);
        memcpy(r457, r473, sizeof(int32_t) * 200);
    }
    memcpy(r474, r455, sizeof(int32_t) * 1);
    memcpy(r475, r456, sizeof(int32_t) * 200);
    memcpy(r476, r457, sizeof(int32_t) * 200);
    /* abs [abs] -> r477 */
    for (long i9733 = 0; i9733 < 3200; ++i9733) {
        r477[i9733] = abs32(r449[i9733]);
    }
    /* reduce_max [reduce_max] -> r478 */
    for (long i9734 = 0; i9734 < 200; ++i9734) {
        r478[i9734] = (-2147483647 - 1);
    }
    for (long i9735 = 0; i9735 < 3200; ++i9735) {
        long t9737 = i9735;
        long c97360 = t9737 / 640; t9737 %= 640;
        long c97361 = t9737 / 640; t9737 %= 640;
        long c97362 = t9737 / 16; t9737 %= 16;
        long c97363 = t9737;
        r478[c97360 * 40 + c97361 * 40 + c97362 * 1] = max32(r478[c97360 * 40 + c97361 * 40 + c97362 * 1], r477[i9735]);
    }
    /* sub [sub] -> r479 */
    for (long i9738 = 0; i9738 < 200; ++i9738) {
        r479[i9738] = sub32(r478[i9738], r61[0]);
    }
    /* loop [scan] -> r501 */
    memcpy(r480, r449, sizeof(int32_t) * 3200);
    memcpy(r481, r61, sizeof(int32_t) * 1);
    memcpy(r482, r39, sizeof(int32_t) * 1);
    memcpy(r483, r479, sizeof(int32_t) * 200);
    memcpy(r484, r478, sizeof(int32_t) * 200);
    for (long t9739 = 0; t9739 < 12; ++t9739) {
        /* add [add] -> r485 */
        for (long i10740 = 0; i10740 < 1; ++i10740) {
            r485[i10740] = add32(r482[0], r30[0]);
        }
        /* add [add] -> r486 */
        for (long i10741 = 0; i10741 < 200; ++i10741) {
            r486[i10741] = add32(r483[i10741], r484[i10741]);
        }
        /* shra [shift_right_arithmetic] -> r487 */
        for (long i10742 = 0; i10742 < 200; ++i10742) {
            r487[i10742] = asr32(r486[i10742], 1);
        }
        /* broadcast [broadcast_in_dim] -> r488 */
        for (long i10743 = 0; i10743 < 200; ++i10743) {
            long t10745 = i10743;
            long c107440 = t10745 / 40; t10745 %= 40;
            long c107441 = t10745 / 40; t10745 %= 40;
            long c107442 = t10745 / 1; t10745 %= 1;
            long c107443 = t10745;
            r488[i10743] = r487[c107440 * 40 + c107442 * 1];
        }
        /* sub [sub] -> r489 */
        for (long i10746 = 0; i10746 < 3200; ++i10746) {
            long t10748 = i10746;
            long c107470 = t10748 / 640; t10748 %= 640;
            long c107471 = t10748 / 640; t10748 %= 640;
            long c107472 = t10748 / 16; t10748 %= 16;
            long c107473 = t10748;
            r489[i10746] = sub32(r480[c107470 * 640 + c107472 * 16 + c107473 * 1], r488[c107470 * 40 + c107472 * 1]);
        }
        /* max [max] -> r490 */
        for (long i10749 = 0; i10749 < 3200; ++i10749) {
            r490[i10749] = max32(r489[i10749], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r491 */
        for (long i10750 = 0; i10750 < 200; ++i10750) {
            r491[i10750] = 0;
        }
        for (long i10751 = 0; i10751 < 3200; ++i10751) {
            long t10753 = i10751;
            long c107520 = t10753 / 640; t10753 %= 640;
            long c107521 = t10753 / 640; t10753 %= 640;
            long c107522 = t10753 / 16; t10753 %= 16;
            long c107523 = t10753;
            r491[c107520 * 40 + c107521 * 40 + c107522 * 1] = add32(r491[c107520 * 40 + c107521 * 40 + c107522 * 1], r490[i10751]);
        }
        /* neg [neg] -> r492 */
        for (long i10754 = 0; i10754 < 3200; ++i10754) {
            r492[i10754] = neg32(r480[i10754]);
        }
        /* broadcast [broadcast_in_dim] -> r493 */
        for (long i10755 = 0; i10755 < 200; ++i10755) {
            long t10757 = i10755;
            long c107560 = t10757 / 40; t10757 %= 40;
            long c107561 = t10757 / 40; t10757 %= 40;
            long c107562 = t10757 / 1; t10757 %= 1;
            long c107563 = t10757;
            r493[i10755] = r487[c107560 * 40 + c107562 * 1];
        }
        /* sub [sub] -> r494 */
        for (long i10758 = 0; i10758 < 3200; ++i10758) {
            long t10760 = i10758;
            long c107590 = t10760 / 640; t10760 %= 640;
            long c107591 = t10760 / 640; t10760 %= 640;
            long c107592 = t10760 / 16; t10760 %= 16;
            long c107593 = t10760;
            r494[i10758] = sub32(r492[c107590 * 640 + c107592 * 16 + c107593 * 1], r493[c107590 * 40 + c107592 * 1]);
        }
        /* max [max] -> r495 */
        for (long i10761 = 0; i10761 < 3200; ++i10761) {
            r495[i10761] = max32(r494[i10761], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r496 */
        for (long i10762 = 0; i10762 < 200; ++i10762) {
            r496[i10762] = 0;
        }
        for (long i10763 = 0; i10763 < 3200; ++i10763) {
            long t10765 = i10763;
            long c107640 = t10765 / 640; t10765 %= 640;
            long c107641 = t10765 / 640; t10765 %= 640;
            long c107642 = t10765 / 16; t10765 %= 16;
            long c107643 = t10765;
            r496[c107640 * 40 + c107641 * 40 + c107642 * 1] = add32(r496[c107640 * 40 + c107641 * 40 + c107642 * 1], r495[i10763]);
        }
        /* add [add] -> r497 */
        for (long i10766 = 0; i10766 < 200; ++i10766) {
            r497[i10766] = add32(r491[i10766], r496[i10766]);
        }
        /* gt [gt] -> r498 */
        for (long i10767 = 0; i10767 < 200; ++i10767) {
            r498[i10767] = r497[i10767] > r481[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r499 */
        for (long i10768 = 0; i10768 < 200; ++i10768) {
            r499[i10768] = r498[i10768] == 0 ? r483[i10768] : (r487[i10768]);
        }
        /* select_n [select_n] -> r500 */
        for (long i10769 = 0; i10769 < 200; ++i10769) {
            r500[i10769] = r498[i10769] == 0 ? r487[i10769] : (r484[i10769]);
        }
        memcpy(r482, r485, sizeof(int32_t) * 1);
        memcpy(r483, r499, sizeof(int32_t) * 200);
        memcpy(r484, r500, sizeof(int32_t) * 200);
    }
    memcpy(r501, r482, sizeof(int32_t) * 1);
    memcpy(r502, r483, sizeof(int32_t) * 200);
    memcpy(r503, r484, sizeof(int32_t) * 200);
    /* sub [sub] -> r504 */
    for (long i10770 = 0; i10770 < 200; ++i10770) {
        r504[i10770] = sub32(r476[i10770], r503[i10770]);
    }
    /* transpose [transpose] -> r505 */
    for (long i10771 = 0; i10771 < 200; ++i10771) {
        long t10773 = i10771;
        long c107720 = t10773 / 200; t10773 %= 200;
        long c107721 = t10773 / 40; t10773 %= 40;
        long c107722 = t10773;
        r505[i10771] = r504[c107720 * 40 + c107721 * 40 + c107722 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r506 */
    for (long i10774 = 0; i10774 < 1; ++i10774) {
        long t10776 = i10774;
        long c107750 = t10776 / 1; t10776 %= 1;
        long c107751 = t10776;
        r506[i10774] = r423[0];
    }
    /* max [max] -> r507 */
    for (long i10777 = 0; i10777 < 200; ++i10777) {
        r507[i10777] = max32(r505[i10777], r39[0]);
    }
    /* iota [iota] -> r508 */
    for (long i10778 = 0; i10778 < 200; ++i10778) {
        long t10780 = i10778;
        long c107790 = t10780 / 200; t10780 %= 200;
        long c107791 = t10780 / 40; t10780 %= 40;
        long c107792 = t10780;
        r508[i10778] = (int32_t)c107792;
    }
    /* broadcast [broadcast_in_dim] -> r509 */
    for (long i10781 = 0; i10781 < 1; ++i10781) {
        long t10783 = i10781;
        long c107820 = t10783 / 1; t10783 %= 1;
        long c107821 = t10783 / 1; t10783 %= 1;
        long c107822 = t10783;
        r509[i10781] = r506[0];
    }
    /* lt [lt] -> r510 */
    for (long i10784 = 0; i10784 < 200; ++i10784) {
        long t10786 = i10784;
        long c107850 = t10786 / 200; t10786 %= 200;
        long c107851 = t10786 / 40; t10786 %= 40;
        long c107852 = t10786;
        r510[i10784] = r508[c107851 * 40 + c107852 * 1] < r509[0] ? 1 : 0;
    }
    /* convert [convert_element_type] -> r511 */
    for (long i10787 = 0; i10787 < 1; ++i10787) {
        r511[i10787] = (int32_t)r39[0];
    }
    /* broadcast [broadcast_in_dim] -> r512 */
    for (long i10788 = 0; i10788 < 200; ++i10788) {
        long t10790 = i10788;
        long c107890 = t10790 / 200; t10790 %= 200;
        long c107891 = t10790 / 40; t10790 %= 40;
        long c107892 = t10790;
        r512[i10788] = r511[0];
    }
    /* select_n [select_n] -> r513 */
    for (long i10791 = 0; i10791 < 200; ++i10791) {
        r513[i10791] = r510[i10791] == 0 ? r512[i10791] : (r507[i10791]);
    }
    /* reduce_sum [reduce_sum] -> r514 */
    for (long i10792 = 0; i10792 < 5; ++i10792) {
        r514[i10792] = 0;
    }
    for (long i10793 = 0; i10793 < 200; ++i10793) {
        long t10795 = i10793;
        long c107940 = t10795 / 200; t10795 %= 200;
        long c107941 = t10795 / 40; t10795 %= 40;
        long c107942 = t10795;
        r514[c107940 * 5 + c107941 * 1] = add32(r514[c107940 * 5 + c107941 * 1], r513[i10793]);
    }
    /* shl [shift_left] -> r516 */
    for (long i10796 = 0; i10796 < 5; ++i10796) {
        r516[i10796] = shl32(r514[i10796], 2);
    }
    /* lt [lt] -> r517 */
    for (long i10797 = 0; i10797 < 1; ++i10797) {
        r517[i10797] = r423[i10797] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r518 */
    for (long i10798 = 0; i10798 < 1; ++i10798) {
        r518[i10798] = add32(r423[i10798], r434[0]);
    }
    /* select_n [select_n] -> r519 */
    for (long i10799 = 0; i10799 < 1; ++i10799) {
        r519[i10799] = r517[i10799] == 0 ? r423[i10799] : (r518[i10799]);
    }
    /* broadcast [broadcast_in_dim] -> r520 */
    for (long i10800 = 0; i10800 < 1; ++i10800) {
        long t10802 = i10800;
        long c108010 = t10802 / 1; t10802 %= 1;
        long c108011 = t10802;
        r520[i10800] = r519[0];
    }
    /* gather [gather] -> r521 */
    for (long i10803 = 0; i10803 < 15; ++i10803) {
        long t10805 = i10803;
        long c108040 = t10805 / 15; t10805 %= 15;
        long c108041 = t10805;
        long row10806 = c108040 * 1;
        long s10807 = clamp_start((long)r520[row10806 + 0], 55, 15);
        r521[i10803] = r424[c108040 * 55 + (s10807 + c108041) * 1];
    }
    /* add [add] -> r522 */
    for (long i10808 = 0; i10808 < 1; ++i10808) {
        r522[i10808] = add32(r8[i10808], r423[i10808]);
    }
    /* and [and] -> r523 */
    for (long i10809 = 0; i10809 < 1; ++i10809) {
        r523[i10809] = r8[i10809] & r30[0];
    }
    /* slice [slice] -> r524 */
    for (long i10810 = 0; i10810 < 45; ++i10810) {
        long t10812 = i10810;
        long c108110 = t10812 / 45; t10812 %= 45;
        long c108111 = t10812;
        r524[i10810] = r424[(0 + c108110 * 1) * 55 + (10 + c108111 * 1) * 1];
    }
    /* shl [shift_left] -> r525 */
    for (long i10813 = 0; i10813 < 45; ++i10813) {
        r525[i10813] = shl32(r524[i10813], 1);
    }
    /* convert [convert_element_type] -> r526 */
    for (long i10814 = 0; i10814 < 1; ++i10814) {
        r526[i10814] = (int32_t)r39[0];
    }
    /* pad [pad] -> r527 */
    for (long i10815 = 0; i10815 < 46; ++i10815) {
        r527[i10815] = r526[0];
    }
    for (long i10816 = 0; i10816 < 45; ++i10816) {
        long t10818 = i10816;
        long c108170 = t10818 / 45; t10818 %= 45;
        long c108171 = t10818;
        long d10819 = 0 + c108170 * 1;
        long d10820 = 0 + c108171 * 1;
        if (d10819 >= 0 && d10819 < 1 && d10820 >= 0 && d10820 < 46) r527[d10819 * 46 + d10820 * 1] = r525[i10816];
    }
    /* iota [iota] -> r528 */
    for (long i10821 = 0; i10821 < 20; ++i10821) {
        long t10823 = i10821;
        long c108220 = t10823;
        r528[i10821] = (int32_t)c108220;
    }
    /* shl [shift_left] -> r529 */
    for (long i10824 = 0; i10824 < 20; ++i10824) {
        r529[i10824] = shl32(r528[i10824], 1);
    }
    /* broadcast [broadcast_in_dim] -> r530 */
    for (long i10825 = 0; i10825 < 20; ++i10825) {
        long t10827 = i10825;
        long c108260 = t10827 / 1; t10827 %= 1;
        long c108261 = t10827;
        r530[i10825] = r529[c108260 * 1];
    }
    /* iota [iota] -> r531 */
    for (long i10828 = 0; i10828 < 6; ++i10828) {
        long t10830 = i10828;
        long c108290 = t10830;
        r531[i10828] = (int32_t)c108290;
    }
    /* broadcast [broadcast_in_dim] -> r532 */
    for (long i10831 = 0; i10831 < 6; ++i10831) {
        long t10833 = i10831;
        long c108320 = t10833 / 6; t10833 %= 6;
        long c108321 = t10833;
        r532[i10831] = r531[c108321 * 1];
    }
    /* add [add] -> r533 */
    for (long i10834 = 0; i10834 < 120; ++i10834) {
        long t10836 = i10834;
        long c108350 = t10836 / 6; t10836 %= 6;
        long c108351 = t10836;
        r533[i10834] = add32(r530[c108350 * 1], r532[c108351 * 1]);
    }
    /* broadcast [broadcast_in_dim] -> r534 */
    for (long i10837 = 0; i10837 < 120; ++i10837) {
        long t10839 = i10837;
        long c108380 = t10839 / 120; t10839 %= 120;
        long c108381 = t10839 / 6; t10839 %= 6;
        long c108382 = t10839;
        r534[i10837] = r533[c108381 * 6 + c108382 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r535 */
    for (long i10840 = 0; i10840 < 1; ++i10840) {
        long t10842 = i10840;
        long c108410 = t10842 / 1; t10842 %= 1;
        long c108411 = t10842 / 1; t10842 %= 1;
        long c108412 = t10842;
        r535[i10840] = r523[0];
    }
    /* add [add] -> r536 */
    for (long i10843 = 0; i10843 < 120; ++i10843) {
        long t10845 = i10843;
        long c108440 = t10845 / 120; t10845 %= 120;
        long c108441 = t10845 / 6; t10845 %= 6;
        long c108442 = t10845;
        r536[i10843] = add32(r535[0], r534[c108441 * 6 + c108442 * 1]);
    }
    /* lt [lt] -> r537 */
    for (long i10846 = 0; i10846 < 120; ++i10846) {
        r537[i10846] = r536[i10846] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r539 */
    for (long i10847 = 0; i10847 < 120; ++i10847) {
        r539[i10847] = add32(r536[i10847], r538[0]);
    }
    /* select_n [select_n] -> r540 */
    for (long i10848 = 0; i10848 < 120; ++i10848) {
        r540[i10848] = r537[i10848] == 0 ? r536[i10848] : (r539[i10848]);
    }
    /* broadcast [broadcast_in_dim] -> r541 */
    for (long i10849 = 0; i10849 < 120; ++i10849) {
        long t10851 = i10849;
        long c108500 = t10851 / 120; t10851 %= 120;
        long c108501 = t10851 / 6; t10851 %= 6;
        long c108502 = t10851 / 1; t10851 %= 1;
        long c108503 = t10851;
        r541[i10849] = r540[c108501 * 6 + c108502 * 1];
    }
    /* gather [gather] -> r542 */
    for (long i10852 = 0; i10852 < 120; ++i10852) {
        long t10854 = i10852;
        long c108530 = t10854 / 120; t10854 %= 120;
        long c108531 = t10854 / 6; t10854 %= 6;
        long c108532 = t10854;
        long row10855 = c108530 * 120 + c108531 * 6 + c108532 * 1;
        long s10856 = clamp_start((long)r541[row10855 + 0], 46, 1);
        r542[i10852] = r527[c108530 * 46 + s10856 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r543 */
    for (long i10857 = 0; i10857 < 6; ++i10857) {
        long t10859 = i10857;
        long c108580 = t10859 / 6; t10859 %= 6;
        long c108581 = t10859 / 6; t10859 %= 6;
        long c108582 = t10859;
        r543[i10857] = r19[c108582 * 1];
    }
    /* add [add] -> r544 */
    for (long i10860 = 0; i10860 < 120; ++i10860) {
        long t10862 = i10860;
        long c108610 = t10862 / 120; t10862 %= 120;
        long c108611 = t10862 / 6; t10862 %= 6;
        long c108612 = t10862;
        r544[i10860] = add32(r543[c108612 * 1], r542[c108611 * 6 + c108612 * 1]);
    }
    /* convert [convert_element_type] -> r545 */
    for (long i10863 = 0; i10863 < 1; ++i10863) {
        r545[i10863] = (int32_t)r48[0];
    }
    /* max [max] -> r546 */
    for (long i10864 = 0; i10864 < 120; ++i10864) {
        r546[i10864] = max32(r545[0], r544[i10864]);
    }
    /* convert [convert_element_type] -> r547 */
    for (long i10865 = 0; i10865 < 1; ++i10865) {
        r547[i10865] = (int32_t)r49[0];
    }
    /* min [min] -> r548 */
    for (long i10866 = 0; i10866 < 120; ++i10866) {
        r548[i10866] = min32(r547[0], r546[i10866]);
    }
    /* broadcast [broadcast_in_dim] -> r549 */
    for (long i10867 = 0; i10867 < 6; ++i10867) {
        long t10869 = i10867;
        long c108680 = t10869 / 6; t10869 %= 6;
        long c108681 = t10869 / 6; t10869 %= 6;
        long c108682 = t10869;
        r549[i10867] = r19[c108682 * 1];
    }
    /* sub [sub] -> r550 */
    for (long i10870 = 0; i10870 < 120; ++i10870) {
        long t10872 = i10870;
        long c108710 = t10872 / 120; t10872 %= 120;
        long c108711 = t10872 / 6; t10872 %= 6;
        long c108712 = t10872;
        r550[i10870] = sub32(r549[c108712 * 1], r542[c108711 * 6 + c108712 * 1]);
    }
    /* convert [convert_element_type] -> r551 */
    for (long i10873 = 0; i10873 < 1; ++i10873) {
        r551[i10873] = (int32_t)r48[0];
    }
    /* max [max] -> r552 */
    for (long i10874 = 0; i10874 < 120; ++i10874) {
        r552[i10874] = max32(r551[0], r550[i10874]);
    }
    /* convert [convert_element_type] -> r553 */
    for (long i10875 = 0; i10875 < 1; ++i10875) {
        r553[i10875] = (int32_t)r49[0];
    }
    /* min [min] -> r554 */
    for (long i10876 = 0; i10876 < 120; ++i10876) {
        r554[i10876] = min32(r553[0], r552[i10876]);
    }
    /* abs [abs] -> r555 */
    for (long i10877 = 0; i10877 < 120; ++i10877) {
        r555[i10877] = abs32(r548[i10877]);
    }
    /* reduce_max [reduce_max] -> r556 */
    for (long i10878 = 0; i10878 < 20; ++i10878) {
        r556[i10878] = (-2147483647 - 1);
    }
    for (long i10879 = 0; i10879 < 120; ++i10879) {
        long t10881 = i10879;
        long c108800 = t10881 / 120; t10881 %= 120;
        long c108801 = t10881 / 6; t10881 %= 6;
        long c108802 = t10881;
        r556[c108800 * 20 + c108801 * 1] = max32(r556[c108800 * 20 + c108801 * 1], r555[i10879]);
    }
    /* sub [sub] -> r557 */
    for (long i10882 = 0; i10882 < 20; ++i10882) {
        r557[i10882] = sub32(r556[i10882], r61[0]);
    }
    /* loop [scan] -> r579 */
    memcpy(r558, r548, sizeof(int32_t) * 120);
    memcpy(r559, r61, sizeof(int32_t) * 1);
    memcpy(r560, r39, sizeof(int32_t) * 1);
    memcpy(r561, r557, sizeof(int32_t) * 20);
    memcpy(r562, r556, sizeof(int32_t) * 20);
    for (long t10883 = 0; t10883 < 12; ++t10883) {
        /* add [add] -> r563 */
        for (long i11884 = 0; i11884 < 1; ++i11884) {
            r563[i11884] = add32(r560[0], r30[0]);
        }
        /* add [add] -> r564 */
        for (long i11885 = 0; i11885 < 20; ++i11885) {
            r564[i11885] = add32(r561[i11885], r562[i11885]);
        }
        /* shra [shift_right_arithmetic] -> r565 */
        for (long i11886 = 0; i11886 < 20; ++i11886) {
            r565[i11886] = asr32(r564[i11886], 1);
        }
        /* broadcast [broadcast_in_dim] -> r566 */
        for (long i11887 = 0; i11887 < 20; ++i11887) {
            long t11889 = i11887;
            long c118880 = t11889 / 20; t11889 %= 20;
            long c118881 = t11889 / 1; t11889 %= 1;
            long c118882 = t11889;
            r566[i11887] = r565[c118881 * 1];
        }
        /* sub [sub] -> r567 */
        for (long i11890 = 0; i11890 < 120; ++i11890) {
            long t11892 = i11890;
            long c118910 = t11892 / 120; t11892 %= 120;
            long c118911 = t11892 / 6; t11892 %= 6;
            long c118912 = t11892;
            r567[i11890] = sub32(r558[c118911 * 6 + c118912 * 1], r566[c118911 * 1]);
        }
        /* max [max] -> r568 */
        for (long i11893 = 0; i11893 < 120; ++i11893) {
            r568[i11893] = max32(r567[i11893], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r569 */
        for (long i11894 = 0; i11894 < 20; ++i11894) {
            r569[i11894] = 0;
        }
        for (long i11895 = 0; i11895 < 120; ++i11895) {
            long t11897 = i11895;
            long c118960 = t11897 / 120; t11897 %= 120;
            long c118961 = t11897 / 6; t11897 %= 6;
            long c118962 = t11897;
            r569[c118960 * 20 + c118961 * 1] = add32(r569[c118960 * 20 + c118961 * 1], r568[i11895]);
        }
        /* neg [neg] -> r570 */
        for (long i11898 = 0; i11898 < 120; ++i11898) {
            r570[i11898] = neg32(r558[i11898]);
        }
        /* broadcast [broadcast_in_dim] -> r571 */
        for (long i11899 = 0; i11899 < 20; ++i11899) {
            long t11901 = i11899;
            long c119000 = t11901 / 20; t11901 %= 20;
            long c119001 = t11901 / 1; t11901 %= 1;
            long c119002 = t11901;
            r571[i11899] = r565[c119001 * 1];
        }
        /* sub [sub] -> r572 */
        for (long i11902 = 0; i11902 < 120; ++i11902) {
            long t11904 = i11902;
            long c119030 = t11904 / 120; t11904 %= 120;
            long c119031 = t11904 / 6; t11904 %= 6;
            long c119032 = t11904;
            r572[i11902] = sub32(r570[c119031 * 6 + c119032 * 1], r571[c119031 * 1]);
        }
        /* max [max] -> r573 */
        for (long i11905 = 0; i11905 < 120; ++i11905) {
            r573[i11905] = max32(r572[i11905], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r574 */
        for (long i11906 = 0; i11906 < 20; ++i11906) {
            r574[i11906] = 0;
        }
        for (long i11907 = 0; i11907 < 120; ++i11907) {
            long t11909 = i11907;
            long c119080 = t11909 / 120; t11909 %= 120;
            long c119081 = t11909 / 6; t11909 %= 6;
            long c119082 = t11909;
            r574[c119080 * 20 + c119081 * 1] = add32(r574[c119080 * 20 + c119081 * 1], r573[i11907]);
        }
        /* add [add] -> r575 */
        for (long i11910 = 0; i11910 < 20; ++i11910) {
            r575[i11910] = add32(r569[i11910], r574[i11910]);
        }
        /* gt [gt] -> r576 */
        for (long i11911 = 0; i11911 < 20; ++i11911) {
            r576[i11911] = r575[i11911] > r559[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r577 */
        for (long i11912 = 0; i11912 < 20; ++i11912) {
            r577[i11912] = r576[i11912] == 0 ? r561[i11912] : (r565[i11912]);
        }
        /* select_n [select_n] -> r578 */
        for (long i11913 = 0; i11913 < 20; ++i11913) {
            r578[i11913] = r576[i11913] == 0 ? r565[i11913] : (r562[i11913]);
        }
        memcpy(r560, r563, sizeof(int32_t) * 1);
        memcpy(r561, r577, sizeof(int32_t) * 20);
        memcpy(r562, r578, sizeof(int32_t) * 20);
    }
    memcpy(r579, r560, sizeof(int32_t) * 1);
    memcpy(r580, r561, sizeof(int32_t) * 20);
    memcpy(r581, r562, sizeof(int32_t) * 20);
    /* abs [abs] -> r582 */
    for (long i11914 = 0; i11914 < 120; ++i11914) {
        r582[i11914] = abs32(r554[i11914]);
    }
    /* reduce_max [reduce_max] -> r583 */
    for (long i11915 = 0; i11915 < 20; ++i11915) {
        r583[i11915] = (-2147483647 - 1);
    }
    for (long i11916 = 0; i11916 < 120; ++i11916) {
        long t11918 = i11916;
        long c119170 = t11918 / 120; t11918 %= 120;
        long c119171 = t11918 / 6; t11918 %= 6;
        long c119172 = t11918;
        r583[c119170 * 20 + c119171 * 1] = max32(r583[c119170 * 20 + c119171 * 1], r582[i11916]);
    }
    /* sub [sub] -> r584 */
    for (long i11919 = 0; i11919 < 20; ++i11919) {
        r584[i11919] = sub32(r583[i11919], r61[0]);
    }
    /* loop [scan] -> r606 */
    memcpy(r585, r554, sizeof(int32_t) * 120);
    memcpy(r586, r61, sizeof(int32_t) * 1);
    memcpy(r587, r39, sizeof(int32_t) * 1);
    memcpy(r588, r584, sizeof(int32_t) * 20);
    memcpy(r589, r583, sizeof(int32_t) * 20);
    for (long t11920 = 0; t11920 < 12; ++t11920) {
        /* add [add] -> r590 */
        for (long i12921 = 0; i12921 < 1; ++i12921) {
            r590[i12921] = add32(r587[0], r30[0]);
        }
        /* add [add] -> r591 */
        for (long i12922 = 0; i12922 < 20; ++i12922) {
            r591[i12922] = add32(r588[i12922], r589[i12922]);
        }
        /* shra [shift_right_arithmetic] -> r592 */
        for (long i12923 = 0; i12923 < 20; ++i12923) {
            r592[i12923] = asr32(r591[i12923], 1);
        }
        /* broadcast [broadcast_in_dim] -> r593 */
        for (long i12924 = 0; i12924 < 20; ++i12924) {
            long t12926 = i12924;
            long c129250 = t12926 / 20; t12926 %= 20;
            long c129251 = t12926 / 1; t12926 %= 1;
            long c129252 = t12926;
            r593[i12924] = r592[c129251 * 1];
        }
        /* sub [sub] -> r594 */
        for (long i12927 = 0; i12927 < 120; ++i12927) {
            long t12929 = i12927;
            long c129280 = t12929 / 120; t12929 %= 120;
            long c129281 = t12929 / 6; t12929 %= 6;
            long c129282 = t12929;
            r594[i12927] = sub32(r585[c129281 * 6 + c129282 * 1], r593[c129281 * 1]);
        }
        /* max [max] -> r595 */
        for (long i12930 = 0; i12930 < 120; ++i12930) {
            r595[i12930] = max32(r594[i12930], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r596 */
        for (long i12931 = 0; i12931 < 20; ++i12931) {
            r596[i12931] = 0;
        }
        for (long i12932 = 0; i12932 < 120; ++i12932) {
            long t12934 = i12932;
            long c129330 = t12934 / 120; t12934 %= 120;
            long c129331 = t12934 / 6; t12934 %= 6;
            long c129332 = t12934;
            r596[c129330 * 20 + c129331 * 1] = add32(r596[c129330 * 20 + c129331 * 1], r595[i12932]);
        }
        /* neg [neg] -> r597 */
        for (long i12935 = 0; i12935 < 120; ++i12935) {
            r597[i12935] = neg32(r585[i12935]);
        }
        /* broadcast [broadcast_in_dim] -> r598 */
        for (long i12936 = 0; i12936 < 20; ++i12936) {
            long t12938 = i12936;
            long c129370 = t12938 / 20; t12938 %= 20;
            long c129371 = t12938 / 1; t12938 %= 1;
            long c129372 = t12938;
            r598[i12936] = r592[c129371 * 1];
        }
        /* sub [sub] -> r599 */
        for (long i12939 = 0; i12939 < 120; ++i12939) {
            long t12941 = i12939;
            long c129400 = t12941 / 120; t12941 %= 120;
            long c129401 = t12941 / 6; t12941 %= 6;
            long c129402 = t12941;
            r599[i12939] = sub32(r597[c129401 * 6 + c129402 * 1], r598[c129401 * 1]);
        }
        /* max [max] -> r600 */
        for (long i12942 = 0; i12942 < 120; ++i12942) {
            r600[i12942] = max32(r599[i12942], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r601 */
        for (long i12943 = 0; i12943 < 20; ++i12943) {
            r601[i12943] = 0;
        }
        for (long i12944 = 0; i12944 < 120; ++i12944) {
            long t12946 = i12944;
            long c129450 = t12946 / 120; t12946 %= 120;
            long c129451 = t12946 / 6; t12946 %= 6;
            long c129452 = t12946;
            r601[c129450 * 20 + c129451 * 1] = add32(r601[c129450 * 20 + c129451 * 1], r600[i12944]);
        }
        /* add [add] -> r602 */
        for (long i12947 = 0; i12947 < 20; ++i12947) {
            r602[i12947] = add32(r596[i12947], r601[i12947]);
        }
        /* gt [gt] -> r603 */
        for (long i12948 = 0; i12948 < 20; ++i12948) {
            r603[i12948] = r602[i12948] > r586[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r604 */
        for (long i12949 = 0; i12949 < 20; ++i12949) {
            r604[i12949] = r603[i12949] == 0 ? r588[i12949] : (r592[i12949]);
        }
        /* select_n [select_n] -> r605 */
        for (long i12950 = 0; i12950 < 20; ++i12950) {
            r605[i12950] = r603[i12950] == 0 ? r592[i12950] : (r589[i12950]);
        }
        memcpy(r587, r590, sizeof(int32_t) * 1);
        memcpy(r588, r604, sizeof(int32_t) * 20);
        memcpy(r589, r605, sizeof(int32_t) * 20);
    }
    memcpy(r606, r587, sizeof(int32_t) * 1);
    memcpy(r607, r588, sizeof(int32_t) * 20);
    memcpy(r608, r589, sizeof(int32_t) * 20);
    /* sub [sub] -> r609 */
    for (long i12951 = 0; i12951 < 20; ++i12951) {
        r609[i12951] = sub32(r581[i12951], r608[i12951]);
    }
    /* shra [shift_right_arithmetic] -> r610 */
    for (long i12952 = 0; i12952 < 20; ++i12952) {
        r610[i12952] = asr32(r609[i12952], 1);
    }
    /* convert [convert_element_type] -> r611 */
    for (long i12953 = 0; i12953 < 1; ++i12953) {
        r611[i12953] = (int32_t)r220[0];
    }
    /* max [max] -> r612 */
    for (long i12954 = 0; i12954 < 20; ++i12954) {
        r612[i12954] = max32(r611[0], r610[i12954]);
    }
    /* convert [convert_element_type] -> r613 */
    for (long i12955 = 0; i12955 < 1; ++i12955) {
        r613[i12955] = (int32_t)r221[0];
    }
    /* min [min] -> r614 */
    for (long i12956 = 0; i12956 < 20; ++i12956) {
        r614[i12956] = min32(r613[0], r612[i12956]);
    }
    /* sub [sub] -> r615 */
    for (long i12957 = 0; i12957 < 1; ++i12957) {
        r615[i12957] = sub32(r423[i12957], r523[i12957]);
    }
    /* add [add] -> r616 */
    for (long i12958 = 0; i12958 < 1; ++i12958) {
        r616[i12958] = add32(r615[i12958], r30[0]);
    }
    /* max [max] -> r617 */
    for (long i12959 = 0; i12959 < 1; ++i12959) {
        r617[i12959] = max32(r616[i12959], r39[0]);
    }
    /* shra [shift_right_arithmetic] -> r618 */
    for (long i12960 = 0; i12960 < 1; ++i12960) {
        r618[i12960] = asr32(r617[i12960], 1);
    }
    /* concat [concatenate] -> r619 */
    for (long i12961 = 0; i12961 < 15; ++i12961) {
        long t12963 = i12961;
        long c129620 = t12963 / 15; t12963 %= 15;
        long c129621 = t12963;
        r619[c129620 * 35 + (c129621 + 0) * 1] = r3[i12961];
    }
    for (long i12964 = 0; i12964 < 20; ++i12964) {
        long t12966 = i12964;
        long c129650 = t12966 / 20; t12966 %= 20;
        long c129651 = t12966;
        r619[c129650 * 35 + (c129651 + 15) * 1] = r614[i12964];
    }
    /* shl [shift_left] -> r620 */
    for (long i12967 = 0; i12967 < 35; ++i12967) {
        r620[i12967] = shl32(r619[i12967], 1);
    }
    /* rev [rev] -> r621 */
    for (long i12968 = 0; i12968 < 80; ++i12968) {
        long t12970 = i12968;
        long c129690 = t12970 / 16; t12970 %= 16;
        long c129691 = t12970;
        r621[i12968] = r18[c129690 * 16 + (16 - 1 - c129691) * 1];
    }
    /* reshape [reshape] -> r622 */
    memcpy(r622, r621, sizeof(int32_t) * 80);
    /* iota [iota] -> r623 */
    for (long i12971 = 0; i12971 < 20; ++i12971) {
        long t12973 = i12971;
        long c129720 = t12973;
        r623[i12971] = (int32_t)c129720;
    }
    /* broadcast [broadcast_in_dim] -> r624 */
    for (long i12974 = 0; i12974 < 20; ++i12974) {
        long t12976 = i12974;
        long c129750 = t12976 / 1; t12976 %= 1;
        long c129751 = t12976;
        r624[i12974] = r623[c129750 * 1];
    }
    /* iota [iota] -> r625 */
    for (long i12977 = 0; i12977 < 16; ++i12977) {
        long t12979 = i12977;
        long c129780 = t12979;
        r625[i12977] = (int32_t)c129780;
    }
    /* broadcast [broadcast_in_dim] -> r626 */
    for (long i12980 = 0; i12980 < 16; ++i12980) {
        long t12982 = i12980;
        long c129810 = t12982 / 16; t12982 %= 16;
        long c129811 = t12982;
        r626[i12980] = r625[c129811 * 1];
    }
    /* add [add] -> r627 */
    for (long i12983 = 0; i12983 < 320; ++i12983) {
        long t12985 = i12983;
        long c129840 = t12985 / 16; t12985 %= 16;
        long c129841 = t12985;
        r627[i12983] = add32(r624[c129840 * 1], r626[c129841 * 1]);
    }
    /* lt [lt] -> r628 */
    for (long i12986 = 0; i12986 < 320; ++i12986) {
        r628[i12986] = r627[i12986] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r630 */
    for (long i12987 = 0; i12987 < 320; ++i12987) {
        r630[i12987] = add32(r627[i12987], r629[0]);
    }
    /* select_n [select_n] -> r631 */
    for (long i12988 = 0; i12988 < 320; ++i12988) {
        r631[i12988] = r628[i12988] == 0 ? r627[i12988] : (r630[i12988]);
    }
    /* broadcast [broadcast_in_dim] -> r632 */
    for (long i12989 = 0; i12989 < 320; ++i12989) {
        long t12991 = i12989;
        long c129900 = t12991 / 16; t12991 %= 16;
        long c129901 = t12991 / 1; t12991 %= 1;
        long c129902 = t12991;
        r632[i12989] = r631[c129900 * 16 + c129901 * 1];
    }
    /* gather [gather] -> r633 */
    for (long i12992 = 0; i12992 < 320; ++i12992) {
        long t12994 = i12992;
        long c129930 = t12994 / 320; t12994 %= 320;
        long c129931 = t12994 / 16; t12994 %= 16;
        long c129932 = t12994;
        long row12995 = c129931 * 16 + c129932 * 1;
        long s12996 = clamp_start((long)r632[row12995 + 0], 35, 1);
        r633[i12992] = r620[c129930 * 35 + s12996 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r634 */
    for (long i12997 = 0; i12997 < 320; ++i12997) {
        long t12999 = i12997;
        long c129980 = t12999 / 320; t12999 %= 320;
        long c129981 = t12999 / 320; t12999 %= 320;
        long c129982 = t12999 / 16; t12999 %= 16;
        long c129983 = t12999;
        r634[i12997] = r633[c129982 * 16 + c129983 * 1];
    }
    /* add [add] -> r635 */
    for (long i13000 = 0; i13000 < 1600; ++i13000) {
        long t13002 = i13000;
        long c130010 = t13002 / 320; t13002 %= 320;
        long c130011 = t13002 / 320; t13002 %= 320;
        long c130012 = t13002 / 16; t13002 %= 16;
        long c130013 = t13002;
        r635[i13000] = add32(r622[c130010 * 16 + c130013 * 1], r634[c130012 * 16 + c130013 * 1]);
    }
    /* convert [convert_element_type] -> r636 */
    for (long i13003 = 0; i13003 < 1; ++i13003) {
        r636[i13003] = (int32_t)r48[0];
    }
    /* max [max] -> r637 */
    for (long i13004 = 0; i13004 < 1600; ++i13004) {
        r637[i13004] = max32(r636[0], r635[i13004]);
    }
    /* convert [convert_element_type] -> r638 */
    for (long i13005 = 0; i13005 < 1; ++i13005) {
        r638[i13005] = (int32_t)r49[0];
    }
    /* min [min] -> r639 */
    for (long i13006 = 0; i13006 < 1600; ++i13006) {
        r639[i13006] = min32(r638[0], r637[i13006]);
    }
    /* sub [sub] -> r640 */
    for (long i13007 = 0; i13007 < 1600; ++i13007) {
        long t13009 = i13007;
        long c130080 = t13009 / 320; t13009 %= 320;
        long c130081 = t13009 / 320; t13009 %= 320;
        long c130082 = t13009 / 16; t13009 %= 16;
        long c130083 = t13009;
        r640[i13007] = sub32(r622[c130080 * 16 + c130083 * 1], r634[c130082 * 16 + c130083 * 1]);
    }
    /* convert [convert_element_type] -> r641 */
    for (long i13010 = 0; i13010 < 1; ++i13010) {
        r641[i13010] = (int32_t)r48[0];
    }
    /* max [max] -> r642 */
    for (long i13011 = 0; i13011 < 1600; ++i13011) {
        r642[i13011] = max32(r641[0], r640[i13011]);
    }
    /* convert [convert_element_type] -> r643 */
    for (long i13012 = 0; i13012 < 1; ++i13012) {
        r643[i13012] = (int32_t)r49[0];
    }
    /* min [min] -> r644 */
    for (long i13013 = 0; i13013 < 1600; ++i13013) {
        r644[i13013] = min32(r643[0], r642[i13013]);
    }
    /* abs [abs] -> r645 */
    for (long i13014 = 0; i13014 < 1600; ++i13014) {
        r645[i13014] = abs32(r639[i13014]);
    }
    /* reduce_max [reduce_max] -> r646 */
    for (long i13015 = 0; i13015 < 100; ++i13015) {
        r646[i13015] = (-2147483647 - 1);
    }
    for (long i13016 = 0; i13016 < 1600; ++i13016) {
        long t13018 = i13016;
        long c130170 = t13018 / 320; t13018 %= 320;
        long c130171 = t13018 / 320; t13018 %= 320;
        long c130172 = t13018 / 16; t13018 %= 16;
        long c130173 = t13018;
        r646[c130170 * 20 + c130171 * 20 + c130172 * 1] = max32(r646[c130170 * 20 + c130171 * 20 + c130172 * 1], r645[i13016]);
    }
    /* sub [sub] -> r647 */
    for (long i13019 = 0; i13019 < 100; ++i13019) {
        r647[i13019] = sub32(r646[i13019], r61[0]);
    }
    /* loop [scan] -> r669 */
    memcpy(r648, r639, sizeof(int32_t) * 1600);
    memcpy(r649, r61, sizeof(int32_t) * 1);
    memcpy(r650, r39, sizeof(int32_t) * 1);
    memcpy(r651, r647, sizeof(int32_t) * 100);
    memcpy(r652, r646, sizeof(int32_t) * 100);
    for (long t13020 = 0; t13020 < 12; ++t13020) {
        /* add [add] -> r653 */
        for (long i14021 = 0; i14021 < 1; ++i14021) {
            r653[i14021] = add32(r650[0], r30[0]);
        }
        /* add [add] -> r654 */
        for (long i14022 = 0; i14022 < 100; ++i14022) {
            r654[i14022] = add32(r651[i14022], r652[i14022]);
        }
        /* shra [shift_right_arithmetic] -> r655 */
        for (long i14023 = 0; i14023 < 100; ++i14023) {
            r655[i14023] = asr32(r654[i14023], 1);
        }
        /* broadcast [broadcast_in_dim] -> r656 */
        for (long i14024 = 0; i14024 < 100; ++i14024) {
            long t14026 = i14024;
            long c140250 = t14026 / 20; t14026 %= 20;
            long c140251 = t14026 / 20; t14026 %= 20;
            long c140252 = t14026 / 1; t14026 %= 1;
            long c140253 = t14026;
            r656[i14024] = r655[c140250 * 20 + c140252 * 1];
        }
        /* sub [sub] -> r657 */
        for (long i14027 = 0; i14027 < 1600; ++i14027) {
            long t14029 = i14027;
            long c140280 = t14029 / 320; t14029 %= 320;
            long c140281 = t14029 / 320; t14029 %= 320;
            long c140282 = t14029 / 16; t14029 %= 16;
            long c140283 = t14029;
            r657[i14027] = sub32(r648[c140280 * 320 + c140282 * 16 + c140283 * 1], r656[c140280 * 20 + c140282 * 1]);
        }
        /* max [max] -> r658 */
        for (long i14030 = 0; i14030 < 1600; ++i14030) {
            r658[i14030] = max32(r657[i14030], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r659 */
        for (long i14031 = 0; i14031 < 100; ++i14031) {
            r659[i14031] = 0;
        }
        for (long i14032 = 0; i14032 < 1600; ++i14032) {
            long t14034 = i14032;
            long c140330 = t14034 / 320; t14034 %= 320;
            long c140331 = t14034 / 320; t14034 %= 320;
            long c140332 = t14034 / 16; t14034 %= 16;
            long c140333 = t14034;
            r659[c140330 * 20 + c140331 * 20 + c140332 * 1] = add32(r659[c140330 * 20 + c140331 * 20 + c140332 * 1], r658[i14032]);
        }
        /* neg [neg] -> r660 */
        for (long i14035 = 0; i14035 < 1600; ++i14035) {
            r660[i14035] = neg32(r648[i14035]);
        }
        /* broadcast [broadcast_in_dim] -> r661 */
        for (long i14036 = 0; i14036 < 100; ++i14036) {
            long t14038 = i14036;
            long c140370 = t14038 / 20; t14038 %= 20;
            long c140371 = t14038 / 20; t14038 %= 20;
            long c140372 = t14038 / 1; t14038 %= 1;
            long c140373 = t14038;
            r661[i14036] = r655[c140370 * 20 + c140372 * 1];
        }
        /* sub [sub] -> r662 */
        for (long i14039 = 0; i14039 < 1600; ++i14039) {
            long t14041 = i14039;
            long c140400 = t14041 / 320; t14041 %= 320;
            long c140401 = t14041 / 320; t14041 %= 320;
            long c140402 = t14041 / 16; t14041 %= 16;
            long c140403 = t14041;
            r662[i14039] = sub32(r660[c140400 * 320 + c140402 * 16 + c140403 * 1], r661[c140400 * 20 + c140402 * 1]);
        }
        /* max [max] -> r663 */
        for (long i14042 = 0; i14042 < 1600; ++i14042) {
            r663[i14042] = max32(r662[i14042], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r664 */
        for (long i14043 = 0; i14043 < 100; ++i14043) {
            r664[i14043] = 0;
        }
        for (long i14044 = 0; i14044 < 1600; ++i14044) {
            long t14046 = i14044;
            long c140450 = t14046 / 320; t14046 %= 320;
            long c140451 = t14046 / 320; t14046 %= 320;
            long c140452 = t14046 / 16; t14046 %= 16;
            long c140453 = t14046;
            r664[c140450 * 20 + c140451 * 20 + c140452 * 1] = add32(r664[c140450 * 20 + c140451 * 20 + c140452 * 1], r663[i14044]);
        }
        /* add [add] -> r665 */
        for (long i14047 = 0; i14047 < 100; ++i14047) {
            r665[i14047] = add32(r659[i14047], r664[i14047]);
        }
        /* gt [gt] -> r666 */
        for (long i14048 = 0; i14048 < 100; ++i14048) {
            r666[i14048] = r665[i14048] > r649[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r667 */
        for (long i14049 = 0; i14049 < 100; ++i14049) {
            r667[i14049] = r666[i14049] == 0 ? r651[i14049] : (r655[i14049]);
        }
        /* select_n [select_n] -> r668 */
        for (long i14050 = 0; i14050 < 100; ++i14050) {
            r668[i14050] = r666[i14050] == 0 ? r655[i14050] : (r652[i14050]);
        }
        memcpy(r650, r653, sizeof(int32_t) * 1);
        memcpy(r651, r667, sizeof(int32_t) * 100);
        memcpy(r652, r668, sizeof(int32_t) * 100);
    }
    memcpy(r669, r650, sizeof(int32_t) * 1);
    memcpy(r670, r651, sizeof(int32_t) * 100);
    memcpy(r671, r652, sizeof(int32_t) * 100);
    /* abs [abs] -> r672 */
    for (long i14051 = 0; i14051 < 1600; ++i14051) {
        r672[i14051] = abs32(r644[i14051]);
    }
    /* reduce_max [reduce_max] -> r673 */
    for (long i14052 = 0; i14052 < 100; ++i14052) {
        r673[i14052] = (-2147483647 - 1);
    }
    for (long i14053 = 0; i14053 < 1600; ++i14053) {
        long t14055 = i14053;
        long c140540 = t14055 / 320; t14055 %= 320;
        long c140541 = t14055 / 320; t14055 %= 320;
        long c140542 = t14055 / 16; t14055 %= 16;
        long c140543 = t14055;
        r673[c140540 * 20 + c140541 * 20 + c140542 * 1] = max32(r673[c140540 * 20 + c140541 * 20 + c140542 * 1], r672[i14053]);
    }
    /* sub [sub] -> r674 */
    for (long i14056 = 0; i14056 < 100; ++i14056) {
        r674[i14056] = sub32(r673[i14056], r61[0]);
    }
    /* loop [scan] -> r696 */
    memcpy(r675, r644, sizeof(int32_t) * 1600);
    memcpy(r676, r61, sizeof(int32_t) * 1);
    memcpy(r677, r39, sizeof(int32_t) * 1);
    memcpy(r678, r674, sizeof(int32_t) * 100);
    memcpy(r679, r673, sizeof(int32_t) * 100);
    for (long t14057 = 0; t14057 < 12; ++t14057) {
        /* add [add] -> r680 */
        for (long i15058 = 0; i15058 < 1; ++i15058) {
            r680[i15058] = add32(r677[0], r30[0]);
        }
        /* add [add] -> r681 */
        for (long i15059 = 0; i15059 < 100; ++i15059) {
            r681[i15059] = add32(r678[i15059], r679[i15059]);
        }
        /* shra [shift_right_arithmetic] -> r682 */
        for (long i15060 = 0; i15060 < 100; ++i15060) {
            r682[i15060] = asr32(r681[i15060], 1);
        }
        /* broadcast [broadcast_in_dim] -> r683 */
        for (long i15061 = 0; i15061 < 100; ++i15061) {
            long t15063 = i15061;
            long c150620 = t15063 / 20; t15063 %= 20;
            long c150621 = t15063 / 20; t15063 %= 20;
            long c150622 = t15063 / 1; t15063 %= 1;
            long c150623 = t15063;
            r683[i15061] = r682[c150620 * 20 + c150622 * 1];
        }
        /* sub [sub] -> r684 */
        for (long i15064 = 0; i15064 < 1600; ++i15064) {
            long t15066 = i15064;
            long c150650 = t15066 / 320; t15066 %= 320;
            long c150651 = t15066 / 320; t15066 %= 320;
            long c150652 = t15066 / 16; t15066 %= 16;
            long c150653 = t15066;
            r684[i15064] = sub32(r675[c150650 * 320 + c150652 * 16 + c150653 * 1], r683[c150650 * 20 + c150652 * 1]);
        }
        /* max [max] -> r685 */
        for (long i15067 = 0; i15067 < 1600; ++i15067) {
            r685[i15067] = max32(r684[i15067], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r686 */
        for (long i15068 = 0; i15068 < 100; ++i15068) {
            r686[i15068] = 0;
        }
        for (long i15069 = 0; i15069 < 1600; ++i15069) {
            long t15071 = i15069;
            long c150700 = t15071 / 320; t15071 %= 320;
            long c150701 = t15071 / 320; t15071 %= 320;
            long c150702 = t15071 / 16; t15071 %= 16;
            long c150703 = t15071;
            r686[c150700 * 20 + c150701 * 20 + c150702 * 1] = add32(r686[c150700 * 20 + c150701 * 20 + c150702 * 1], r685[i15069]);
        }
        /* neg [neg] -> r687 */
        for (long i15072 = 0; i15072 < 1600; ++i15072) {
            r687[i15072] = neg32(r675[i15072]);
        }
        /* broadcast [broadcast_in_dim] -> r688 */
        for (long i15073 = 0; i15073 < 100; ++i15073) {
            long t15075 = i15073;
            long c150740 = t15075 / 20; t15075 %= 20;
            long c150741 = t15075 / 20; t15075 %= 20;
            long c150742 = t15075 / 1; t15075 %= 1;
            long c150743 = t15075;
            r688[i15073] = r682[c150740 * 20 + c150742 * 1];
        }
        /* sub [sub] -> r689 */
        for (long i15076 = 0; i15076 < 1600; ++i15076) {
            long t15078 = i15076;
            long c150770 = t15078 / 320; t15078 %= 320;
            long c150771 = t15078 / 320; t15078 %= 320;
            long c150772 = t15078 / 16; t15078 %= 16;
            long c150773 = t15078;
            r689[i15076] = sub32(r687[c150770 * 320 + c150772 * 16 + c150773 * 1], r688[c150770 * 20 + c150772 * 1]);
        }
        /* max [max] -> r690 */
        for (long i15079 = 0; i15079 < 1600; ++i15079) {
            r690[i15079] = max32(r689[i15079], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r691 */
        for (long i15080 = 0; i15080 < 100; ++i15080) {
            r691[i15080] = 0;
        }
        for (long i15081 = 0; i15081 < 1600; ++i15081) {
            long t15083 = i15081;
            long c150820 = t15083 / 320; t15083 %= 320;
            long c150821 = t15083 / 320; t15083 %= 320;
            long c150822 = t15083 / 16; t15083 %= 16;
            long c150823 = t15083;
            r691[c150820 * 20 + c150821 * 20 + c150822 * 1] = add32(r691[c150820 * 20 + c150821 * 20 + c150822 * 1], r690[i15081]);
        }
        /* add [add] -> r692 */
        for (long i15084 = 0; i15084 < 100; ++i15084) {
            r692[i15084] = add32(r686[i15084], r691[i15084]);
        }
        /* gt [gt] -> r693 */
        for (long i15085 = 0; i15085 < 100; ++i15085) {
            r693[i15085] = r692[i15085] > r676[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r694 */
        for (long i15086 = 0; i15086 < 100; ++i15086) {
            r694[i15086] = r693[i15086] == 0 ? r678[i15086] : (r682[i15086]);
        }
        /* select_n [select_n] -> r695 */
        for (long i15087 = 0; i15087 < 100; ++i15087) {
            r695[i15087] = r693[i15087] == 0 ? r682[i15087] : (r679[i15087]);
        }
        memcpy(r677, r680, sizeof(int32_t) * 1);
        memcpy(r678, r694, sizeof(int32_t) * 100);
        memcpy(r679, r695, sizeof(int32_t) * 100);
    }
    memcpy(r696, r677, sizeof(int32_t) * 1);
    memcpy(r697, r678, sizeof(int32_t) * 100);
    memcpy(r698, r679, sizeof(int32_t) * 100);
    /* sub [sub] -> r699 */
    for (long i15088 = 0; i15088 < 100; ++i15088) {
        r699[i15088] = sub32(r671[i15088], r698[i15088]);
    }
    /* transpose [transpose] -> r700 */
    for (long i15089 = 0; i15089 < 100; ++i15089) {
        long t15091 = i15089;
        long c150900 = t15091 / 100; t15091 %= 100;
        long c150901 = t15091 / 20; t15091 %= 20;
        long c150902 = t15091;
        r700[i15089] = r699[c150900 * 20 + c150901 * 20 + c150902 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r701 */
    for (long i15092 = 0; i15092 < 1; ++i15092) {
        long t15094 = i15092;
        long c150930 = t15094 / 1; t15094 %= 1;
        long c150931 = t15094;
        r701[i15092] = r618[0];
    }
    /* max [max] -> r702 */
    for (long i15095 = 0; i15095 < 100; ++i15095) {
        r702[i15095] = max32(r700[i15095], r39[0]);
    }
    /* iota [iota] -> r703 */
    for (long i15096 = 0; i15096 < 100; ++i15096) {
        long t15098 = i15096;
        long c150970 = t15098 / 100; t15098 %= 100;
        long c150971 = t15098 / 20; t15098 %= 20;
        long c150972 = t15098;
        r703[i15096] = (int32_t)c150972;
    }
    /* broadcast [broadcast_in_dim] -> r704 */
    for (long i15099 = 0; i15099 < 1; ++i15099) {
        long t15101 = i15099;
        long c151000 = t15101 / 1; t15101 %= 1;
        long c151001 = t15101 / 1; t15101 %= 1;
        long c151002 = t15101;
        r704[i15099] = r701[0];
    }
    /* lt [lt] -> r705 */
    for (long i15102 = 0; i15102 < 100; ++i15102) {
        long t15104 = i15102;
        long c151030 = t15104 / 100; t15104 %= 100;
        long c151031 = t15104 / 20; t15104 %= 20;
        long c151032 = t15104;
        r705[i15102] = r703[c151031 * 20 + c151032 * 1] < r704[0] ? 1 : 0;
    }
    /* convert [convert_element_type] -> r706 */
    for (long i15105 = 0; i15105 < 1; ++i15105) {
        r706[i15105] = (int32_t)r39[0];
    }
    /* broadcast [broadcast_in_dim] -> r707 */
    for (long i15106 = 0; i15106 < 100; ++i15106) {
        long t15108 = i15106;
        long c151070 = t15108 / 100; t15108 %= 100;
        long c151071 = t15108 / 20; t15108 %= 20;
        long c151072 = t15108;
        r707[i15106] = r706[0];
    }
    /* select_n [select_n] -> r708 */
    for (long i15109 = 0; i15109 < 100; ++i15109) {
        r708[i15109] = r705[i15109] == 0 ? r707[i15109] : (r702[i15109]);
    }
    /* reduce_sum [reduce_sum] -> r709 */
    for (long i15110 = 0; i15110 < 5; ++i15110) {
        r709[i15110] = 0;
    }
    for (long i15111 = 0; i15111 < 100; ++i15111) {
        long t15113 = i15111;
        long c151120 = t15113 / 100; t15113 %= 100;
        long c151121 = t15113 / 20; t15113 %= 20;
        long c151122 = t15113;
        r709[c151120 * 5 + c151121 * 1] = add32(r709[c151120 * 5 + c151121 * 1], r708[i15111]);
    }
    /* shl [shift_left] -> r711 */
    for (long i15114 = 0; i15114 < 5; ++i15114) {
        r711[i15114] = shl32(r709[i15114], 3);
    }
    /* lt [lt] -> r712 */
    for (long i15115 = 0; i15115 < 1; ++i15115) {
        r712[i15115] = r618[i15115] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r713 */
    for (long i15116 = 0; i15116 < 1; ++i15116) {
        r713[i15116] = add32(r618[i15116], r629[0]);
    }
    /* select_n [select_n] -> r714 */
    for (long i15117 = 0; i15117 < 1; ++i15117) {
        r714[i15117] = r712[i15117] == 0 ? r618[i15117] : (r713[i15117]);
    }
    /* broadcast [broadcast_in_dim] -> r715 */
    for (long i15118 = 0; i15118 < 1; ++i15118) {
        long t15120 = i15118;
        long c151190 = t15120 / 1; t15120 %= 1;
        long c151191 = t15120;
        r715[i15118] = r714[0];
    }
    /* gather [gather] -> r716 */
    for (long i15121 = 0; i15121 < 15; ++i15121) {
        long t15123 = i15121;
        long c151220 = t15123 / 15; t15123 %= 15;
        long c151221 = t15123;
        long row15124 = c151220 * 1;
        long s15125 = clamp_start((long)r715[row15124 + 0], 35, 15);
        r716[i15121] = r619[c151220 * 35 + (s15125 + c151221) * 1];
    }
    /* add [add] -> r717 */
    for (long i15126 = 0; i15126 < 1; ++i15126) {
        r717[i15126] = add32(r9[i15126], r618[i15126]);
    }
    /* and [and] -> r718 */
    for (long i15127 = 0; i15127 < 1; ++i15127) {
        r718[i15127] = r9[i15127] & r30[0];
    }
    /* slice [slice] -> r719 */
    for (long i15128 = 0; i15128 < 25; ++i15128) {
        long t15130 = i15128;
        long c151290 = t15130 / 25; t15130 %= 25;
        long c151291 = t15130;
        r719[i15128] = r619[(0 + c151290 * 1) * 35 + (10 + c151291 * 1) * 1];
    }
    /* shl [shift_left] -> r720 */
    for (long i15131 = 0; i15131 < 25; ++i15131) {
        r720[i15131] = shl32(r719[i15131], 1);
    }
    /* convert [convert_element_type] -> r721 */
    for (long i15132 = 0; i15132 < 1; ++i15132) {
        r721[i15132] = (int32_t)r39[0];
    }
    /* pad [pad] -> r722 */
    for (long i15133 = 0; i15133 < 26; ++i15133) {
        r722[i15133] = r721[0];
    }
    for (long i15134 = 0; i15134 < 25; ++i15134) {
        long t15136 = i15134;
        long c151350 = t15136 / 25; t15136 %= 25;
        long c151351 = t15136;
        long d15137 = 0 + c151350 * 1;
        long d15138 = 0 + c151351 * 1;
        if (d15137 >= 0 && d15137 < 1 && d15138 >= 0 && d15138 < 26) r722[d15137 * 26 + d15138 * 1] = r720[i15134];
    }
    /* iota [iota] -> r723 */
    for (long i15139 = 0; i15139 < 10; ++i15139) {
        long t15141 = i15139;
        long c151400 = t15141;
        r723[i15139] = (int32_t)c151400;
    }
    /* shl [shift_left] -> r724 */
    for (long i15142 = 0; i15142 < 10; ++i15142) {
        r724[i15142] = shl32(r723[i15142], 1);
    }
    /* broadcast [broadcast_in_dim] -> r725 */
    for (long i15143 = 0; i15143 < 10; ++i15143) {
        long t15145 = i15143;
        long c151440 = t15145 / 1; t15145 %= 1;
        long c151441 = t15145;
        r725[i15143] = r724[c151440 * 1];
    }
    /* iota [iota] -> r726 */
    for (long i15146 = 0; i15146 < 6; ++i15146) {
        long t15148 = i15146;
        long c151470 = t15148;
        r726[i15146] = (int32_t)c151470;
    }
    /* broadcast [broadcast_in_dim] -> r727 */
    for (long i15149 = 0; i15149 < 6; ++i15149) {
        long t15151 = i15149;
        long c151500 = t15151 / 6; t15151 %= 6;
        long c151501 = t15151;
        r727[i15149] = r726[c151501 * 1];
    }
    /* add [add] -> r728 */
    for (long i15152 = 0; i15152 < 60; ++i15152) {
        long t15154 = i15152;
        long c151530 = t15154 / 6; t15154 %= 6;
        long c151531 = t15154;
        r728[i15152] = add32(r725[c151530 * 1], r727[c151531 * 1]);
    }
    /* broadcast [broadcast_in_dim] -> r729 */
    for (long i15155 = 0; i15155 < 60; ++i15155) {
        long t15157 = i15155;
        long c151560 = t15157 / 60; t15157 %= 60;
        long c151561 = t15157 / 6; t15157 %= 6;
        long c151562 = t15157;
        r729[i15155] = r728[c151561 * 6 + c151562 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r730 */
    for (long i15158 = 0; i15158 < 1; ++i15158) {
        long t15160 = i15158;
        long c151590 = t15160 / 1; t15160 %= 1;
        long c151591 = t15160 / 1; t15160 %= 1;
        long c151592 = t15160;
        r730[i15158] = r718[0];
    }
    /* add [add] -> r731 */
    for (long i15161 = 0; i15161 < 60; ++i15161) {
        long t15163 = i15161;
        long c151620 = t15163 / 60; t15163 %= 60;
        long c151621 = t15163 / 6; t15163 %= 6;
        long c151622 = t15163;
        r731[i15161] = add32(r730[0], r729[c151621 * 6 + c151622 * 1]);
    }
    /* lt [lt] -> r732 */
    for (long i15164 = 0; i15164 < 60; ++i15164) {
        r732[i15164] = r731[i15164] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r734 */
    for (long i15165 = 0; i15165 < 60; ++i15165) {
        r734[i15165] = add32(r731[i15165], r733[0]);
    }
    /* select_n [select_n] -> r735 */
    for (long i15166 = 0; i15166 < 60; ++i15166) {
        r735[i15166] = r732[i15166] == 0 ? r731[i15166] : (r734[i15166]);
    }
    /* broadcast [broadcast_in_dim] -> r736 */
    for (long i15167 = 0; i15167 < 60; ++i15167) {
        long t15169 = i15167;
        long c151680 = t15169 / 60; t15169 %= 60;
        long c151681 = t15169 / 6; t15169 %= 6;
        long c151682 = t15169 / 1; t15169 %= 1;
        long c151683 = t15169;
        r736[i15167] = r735[c151681 * 6 + c151682 * 1];
    }
    /* gather [gather] -> r737 */
    for (long i15170 = 0; i15170 < 60; ++i15170) {
        long t15172 = i15170;
        long c151710 = t15172 / 60; t15172 %= 60;
        long c151711 = t15172 / 6; t15172 %= 6;
        long c151712 = t15172;
        long row15173 = c151710 * 60 + c151711 * 6 + c151712 * 1;
        long s15174 = clamp_start((long)r736[row15173 + 0], 26, 1);
        r737[i15170] = r722[c151710 * 26 + s15174 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r738 */
    for (long i15175 = 0; i15175 < 6; ++i15175) {
        long t15177 = i15175;
        long c151760 = t15177 / 6; t15177 %= 6;
        long c151761 = t15177 / 6; t15177 %= 6;
        long c151762 = t15177;
        r738[i15175] = r19[c151762 * 1];
    }
    /* add [add] -> r739 */
    for (long i15178 = 0; i15178 < 60; ++i15178) {
        long t15180 = i15178;
        long c151790 = t15180 / 60; t15180 %= 60;
        long c151791 = t15180 / 6; t15180 %= 6;
        long c151792 = t15180;
        r739[i15178] = add32(r738[c151792 * 1], r737[c151791 * 6 + c151792 * 1]);
    }
    /* convert [convert_element_type] -> r740 */
    for (long i15181 = 0; i15181 < 1; ++i15181) {
        r740[i15181] = (int32_t)r48[0];
    }
    /* max [max] -> r741 */
    for (long i15182 = 0; i15182 < 60; ++i15182) {
        r741[i15182] = max32(r740[0], r739[i15182]);
    }
    /* convert [convert_element_type] -> r742 */
    for (long i15183 = 0; i15183 < 1; ++i15183) {
        r742[i15183] = (int32_t)r49[0];
    }
    /* min [min] -> r743 */
    for (long i15184 = 0; i15184 < 60; ++i15184) {
        r743[i15184] = min32(r742[0], r741[i15184]);
    }
    /* broadcast [broadcast_in_dim] -> r744 */
    for (long i15185 = 0; i15185 < 6; ++i15185) {
        long t15187 = i15185;
        long c151860 = t15187 / 6; t15187 %= 6;
        long c151861 = t15187 / 6; t15187 %= 6;
        long c151862 = t15187;
        r744[i15185] = r19[c151862 * 1];
    }
    /* sub [sub] -> r745 */
    for (long i15188 = 0; i15188 < 60; ++i15188) {
        long t15190 = i15188;
        long c151890 = t15190 / 60; t15190 %= 60;
        long c151891 = t15190 / 6; t15190 %= 6;
        long c151892 = t15190;
        r745[i15188] = sub32(r744[c151892 * 1], r737[c151891 * 6 + c151892 * 1]);
    }
    /* convert [convert_element_type] -> r746 */
    for (long i15191 = 0; i15191 < 1; ++i15191) {
        r746[i15191] = (int32_t)r48[0];
    }
    /* max [max] -> r747 */
    for (long i15192 = 0; i15192 < 60; ++i15192) {
        r747[i15192] = max32(r746[0], r745[i15192]);
    }
    /* convert [convert_element_type] -> r748 */
    for (long i15193 = 0; i15193 < 1; ++i15193) {
        r748[i15193] = (int32_t)r49[0];
    }
    /* min [min] -> r749 */
    for (long i15194 = 0; i15194 < 60; ++i15194) {
        r749[i15194] = min32(r748[0], r747[i15194]);
    }
    /* abs [abs] -> r750 */
    for (long i15195 = 0; i15195 < 60; ++i15195) {
        r750[i15195] = abs32(r743[i15195]);
    }
    /* reduce_max [reduce_max] -> r751 */
    for (long i15196 = 0; i15196 < 10; ++i15196) {
        r751[i15196] = (-2147483647 - 1);
    }
    for (long i15197 = 0; i15197 < 60; ++i15197) {
        long t15199 = i15197;
        long c151980 = t15199 / 60; t15199 %= 60;
        long c151981 = t15199 / 6; t15199 %= 6;
        long c151982 = t15199;
        r751[c151980 * 10 + c151981 * 1] = max32(r751[c151980 * 10 + c151981 * 1], r750[i15197]);
    }
    /* sub [sub] -> r752 */
    for (long i15200 = 0; i15200 < 10; ++i15200) {
        r752[i15200] = sub32(r751[i15200], r61[0]);
    }
    /* loop [scan] -> r774 */
    memcpy(r753, r743, sizeof(int32_t) * 60);
    memcpy(r754, r61, sizeof(int32_t) * 1);
    memcpy(r755, r39, sizeof(int32_t) * 1);
    memcpy(r756, r752, sizeof(int32_t) * 10);
    memcpy(r757, r751, sizeof(int32_t) * 10);
    for (long t15201 = 0; t15201 < 12; ++t15201) {
        /* add [add] -> r758 */
        for (long i16202 = 0; i16202 < 1; ++i16202) {
            r758[i16202] = add32(r755[0], r30[0]);
        }
        /* add [add] -> r759 */
        for (long i16203 = 0; i16203 < 10; ++i16203) {
            r759[i16203] = add32(r756[i16203], r757[i16203]);
        }
        /* shra [shift_right_arithmetic] -> r760 */
        for (long i16204 = 0; i16204 < 10; ++i16204) {
            r760[i16204] = asr32(r759[i16204], 1);
        }
        /* broadcast [broadcast_in_dim] -> r761 */
        for (long i16205 = 0; i16205 < 10; ++i16205) {
            long t16207 = i16205;
            long c162060 = t16207 / 10; t16207 %= 10;
            long c162061 = t16207 / 1; t16207 %= 1;
            long c162062 = t16207;
            r761[i16205] = r760[c162061 * 1];
        }
        /* sub [sub] -> r762 */
        for (long i16208 = 0; i16208 < 60; ++i16208) {
            long t16210 = i16208;
            long c162090 = t16210 / 60; t16210 %= 60;
            long c162091 = t16210 / 6; t16210 %= 6;
            long c162092 = t16210;
            r762[i16208] = sub32(r753[c162091 * 6 + c162092 * 1], r761[c162091 * 1]);
        }
        /* max [max] -> r763 */
        for (long i16211 = 0; i16211 < 60; ++i16211) {
            r763[i16211] = max32(r762[i16211], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r764 */
        for (long i16212 = 0; i16212 < 10; ++i16212) {
            r764[i16212] = 0;
        }
        for (long i16213 = 0; i16213 < 60; ++i16213) {
            long t16215 = i16213;
            long c162140 = t16215 / 60; t16215 %= 60;
            long c162141 = t16215 / 6; t16215 %= 6;
            long c162142 = t16215;
            r764[c162140 * 10 + c162141 * 1] = add32(r764[c162140 * 10 + c162141 * 1], r763[i16213]);
        }
        /* neg [neg] -> r765 */
        for (long i16216 = 0; i16216 < 60; ++i16216) {
            r765[i16216] = neg32(r753[i16216]);
        }
        /* broadcast [broadcast_in_dim] -> r766 */
        for (long i16217 = 0; i16217 < 10; ++i16217) {
            long t16219 = i16217;
            long c162180 = t16219 / 10; t16219 %= 10;
            long c162181 = t16219 / 1; t16219 %= 1;
            long c162182 = t16219;
            r766[i16217] = r760[c162181 * 1];
        }
        /* sub [sub] -> r767 */
        for (long i16220 = 0; i16220 < 60; ++i16220) {
            long t16222 = i16220;
            long c162210 = t16222 / 60; t16222 %= 60;
            long c162211 = t16222 / 6; t16222 %= 6;
            long c162212 = t16222;
            r767[i16220] = sub32(r765[c162211 * 6 + c162212 * 1], r766[c162211 * 1]);
        }
        /* max [max] -> r768 */
        for (long i16223 = 0; i16223 < 60; ++i16223) {
            r768[i16223] = max32(r767[i16223], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r769 */
        for (long i16224 = 0; i16224 < 10; ++i16224) {
            r769[i16224] = 0;
        }
        for (long i16225 = 0; i16225 < 60; ++i16225) {
            long t16227 = i16225;
            long c162260 = t16227 / 60; t16227 %= 60;
            long c162261 = t16227 / 6; t16227 %= 6;
            long c162262 = t16227;
            r769[c162260 * 10 + c162261 * 1] = add32(r769[c162260 * 10 + c162261 * 1], r768[i16225]);
        }
        /* add [add] -> r770 */
        for (long i16228 = 0; i16228 < 10; ++i16228) {
            r770[i16228] = add32(r764[i16228], r769[i16228]);
        }
        /* gt [gt] -> r771 */
        for (long i16229 = 0; i16229 < 10; ++i16229) {
            r771[i16229] = r770[i16229] > r754[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r772 */
        for (long i16230 = 0; i16230 < 10; ++i16230) {
            r772[i16230] = r771[i16230] == 0 ? r756[i16230] : (r760[i16230]);
        }
        /* select_n [select_n] -> r773 */
        for (long i16231 = 0; i16231 < 10; ++i16231) {
            r773[i16231] = r771[i16231] == 0 ? r760[i16231] : (r757[i16231]);
        }
        memcpy(r755, r758, sizeof(int32_t) * 1);
        memcpy(r756, r772, sizeof(int32_t) * 10);
        memcpy(r757, r773, sizeof(int32_t) * 10);
    }
    memcpy(r774, r755, sizeof(int32_t) * 1);
    memcpy(r775, r756, sizeof(int32_t) * 10);
    memcpy(r776, r757, sizeof(int32_t) * 10);
    /* abs [abs] -> r777 */
    for (long i16232 = 0; i16232 < 60; ++i16232) {
        r777[i16232] = abs32(r749[i16232]);
    }
    /* reduce_max [reduce_max] -> r778 */
    for (long i16233 = 0; i16233 < 10; ++i16233) {
        r778[i16233] = (-2147483647 - 1);
    }
    for (long i16234 = 0; i16234 < 60; ++i16234) {
        long t16236 = i16234;
        long c162350 = t16236 / 60; t16236 %= 60;
        long c162351 = t16236 / 6; t16236 %= 6;
        long c162352 = t16236;
        r778[c162350 * 10 + c162351 * 1] = max32(r778[c162350 * 10 + c162351 * 1], r777[i16234]);
    }
    /* sub [sub] -> r779 */
    for (long i16237 = 0; i16237 < 10; ++i16237) {
        r779[i16237] = sub32(r778[i16237], r61[0]);
    }
    /* loop [scan] -> r801 */
    memcpy(r780, r749, sizeof(int32_t) * 60);
    memcpy(r781, r61, sizeof(int32_t) * 1);
    memcpy(r782, r39, sizeof(int32_t) * 1);
    memcpy(r783, r779, sizeof(int32_t) * 10);
    memcpy(r784, r778, sizeof(int32_t) * 10);
    for (long t16238 = 0; t16238 < 12; ++t16238) {
        /* add [add] -> r785 */
        for (long i17239 = 0; i17239 < 1; ++i17239) {
            r785[i17239] = add32(r782[0], r30[0]);
        }
        /* add [add] -> r786 */
        for (long i17240 = 0; i17240 < 10; ++i17240) {
            r786[i17240] = add32(r783[i17240], r784[i17240]);
        }
        /* shra [shift_right_arithmetic] -> r787 */
        for (long i17241 = 0; i17241 < 10; ++i17241) {
            r787[i17241] = asr32(r786[i17241], 1);
        }
        /* broadcast [broadcast_in_dim] -> r788 */
        for (long i17242 = 0; i17242 < 10; ++i17242) {
            long t17244 = i17242;
            long c172430 = t17244 / 10; t17244 %= 10;
            long c172431 = t17244 / 1; t17244 %= 1;
            long c172432 = t17244;
            r788[i17242] = r787[c172431 * 1];
        }
        /* sub [sub] -> r789 */
        for (long i17245 = 0; i17245 < 60; ++i17245) {
            long t17247 = i17245;
            long c172460 = t17247 / 60; t17247 %= 60;
            long c172461 = t17247 / 6; t17247 %= 6;
            long c172462 = t17247;
            r789[i17245] = sub32(r780[c172461 * 6 + c172462 * 1], r788[c172461 * 1]);
        }
        /* max [max] -> r790 */
        for (long i17248 = 0; i17248 < 60; ++i17248) {
            r790[i17248] = max32(r789[i17248], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r791 */
        for (long i17249 = 0; i17249 < 10; ++i17249) {
            r791[i17249] = 0;
        }
        for (long i17250 = 0; i17250 < 60; ++i17250) {
            long t17252 = i17250;
            long c172510 = t17252 / 60; t17252 %= 60;
            long c172511 = t17252 / 6; t17252 %= 6;
            long c172512 = t17252;
            r791[c172510 * 10 + c172511 * 1] = add32(r791[c172510 * 10 + c172511 * 1], r790[i17250]);
        }
        /* neg [neg] -> r792 */
        for (long i17253 = 0; i17253 < 60; ++i17253) {
            r792[i17253] = neg32(r780[i17253]);
        }
        /* broadcast [broadcast_in_dim] -> r793 */
        for (long i17254 = 0; i17254 < 10; ++i17254) {
            long t17256 = i17254;
            long c172550 = t17256 / 10; t17256 %= 10;
            long c172551 = t17256 / 1; t17256 %= 1;
            long c172552 = t17256;
            r793[i17254] = r787[c172551 * 1];
        }
        /* sub [sub] -> r794 */
        for (long i17257 = 0; i17257 < 60; ++i17257) {
            long t17259 = i17257;
            long c172580 = t17259 / 60; t17259 %= 60;
            long c172581 = t17259 / 6; t17259 %= 6;
            long c172582 = t17259;
            r794[i17257] = sub32(r792[c172581 * 6 + c172582 * 1], r793[c172581 * 1]);
        }
        /* max [max] -> r795 */
        for (long i17260 = 0; i17260 < 60; ++i17260) {
            r795[i17260] = max32(r794[i17260], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r796 */
        for (long i17261 = 0; i17261 < 10; ++i17261) {
            r796[i17261] = 0;
        }
        for (long i17262 = 0; i17262 < 60; ++i17262) {
            long t17264 = i17262;
            long c172630 = t17264 / 60; t17264 %= 60;
            long c172631 = t17264 / 6; t17264 %= 6;
            long c172632 = t17264;
            r796[c172630 * 10 + c172631 * 1] = add32(r796[c172630 * 10 + c172631 * 1], r795[i17262]);
        }
        /* add [add] -> r797 */
        for (long i17265 = 0; i17265 < 10; ++i17265) {
            r797[i17265] = add32(r791[i17265], r796[i17265]);
        }
        /* gt [gt] -> r798 */
        for (long i17266 = 0; i17266 < 10; ++i17266) {
            r798[i17266] = r797[i17266] > r781[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r799 */
        for (long i17267 = 0; i17267 < 10; ++i17267) {
            r799[i17267] = r798[i17267] == 0 ? r783[i17267] : (r787[i17267]);
        }
        /* select_n [select_n] -> r800 */
        for (long i17268 = 0; i17268 < 10; ++i17268) {
            r800[i17268] = r798[i17268] == 0 ? r787[i17268] : (r784[i17268]);
        }
        memcpy(r782, r785, sizeof(int32_t) * 1);
        memcpy(r783, r799, sizeof(int32_t) * 10);
        memcpy(r784, r800, sizeof(int32_t) * 10);
    }
    memcpy(r801, r782, sizeof(int32_t) * 1);
    memcpy(r802, r783, sizeof(int32_t) * 10);
    memcpy(r803, r784, sizeof(int32_t) * 10);
    /* sub [sub] -> r804 */
    for (long i17269 = 0; i17269 < 10; ++i17269) {
        r804[i17269] = sub32(r776[i17269], r803[i17269]);
    }
    /* shra [shift_right_arithmetic] -> r805 */
    for (long i17270 = 0; i17270 < 10; ++i17270) {
        r805[i17270] = asr32(r804[i17270], 1);
    }
    /* convert [convert_element_type] -> r806 */
    for (long i17271 = 0; i17271 < 1; ++i17271) {
        r806[i17271] = (int32_t)r220[0];
    }
    /* max [max] -> r807 */
    for (long i17272 = 0; i17272 < 10; ++i17272) {
        r807[i17272] = max32(r806[0], r805[i17272]);
    }
    /* convert [convert_element_type] -> r808 */
    for (long i17273 = 0; i17273 < 1; ++i17273) {
        r808[i17273] = (int32_t)r221[0];
    }
    /* min [min] -> r809 */
    for (long i17274 = 0; i17274 < 10; ++i17274) {
        r809[i17274] = min32(r808[0], r807[i17274]);
    }
    /* sub [sub] -> r810 */
    for (long i17275 = 0; i17275 < 1; ++i17275) {
        r810[i17275] = sub32(r618[i17275], r718[i17275]);
    }
    /* add [add] -> r811 */
    for (long i17276 = 0; i17276 < 1; ++i17276) {
        r811[i17276] = add32(r810[i17276], r30[0]);
    }
    /* max [max] -> r812 */
    for (long i17277 = 0; i17277 < 1; ++i17277) {
        r812[i17277] = max32(r811[i17277], r39[0]);
    }
    /* shra [shift_right_arithmetic] -> r813 */
    for (long i17278 = 0; i17278 < 1; ++i17278) {
        r813[i17278] = asr32(r812[i17278], 1);
    }
    /* concat [concatenate] -> r814 */
    for (long i17279 = 0; i17279 < 15; ++i17279) {
        long t17281 = i17279;
        long c172800 = t17281 / 15; t17281 %= 15;
        long c172801 = t17281;
        r814[c172800 * 25 + (c172801 + 0) * 1] = r4[i17279];
    }
    for (long i17282 = 0; i17282 < 10; ++i17282) {
        long t17284 = i17282;
        long c172830 = t17284 / 10; t17284 %= 10;
        long c172831 = t17284;
        r814[c172830 * 25 + (c172831 + 15) * 1] = r809[i17282];
    }
    /* shl [shift_left] -> r815 */
    for (long i17285 = 0; i17285 < 25; ++i17285) {
        r815[i17285] = shl32(r814[i17285], 1);
    }
    /* rev [rev] -> r816 */
    for (long i17286 = 0; i17286 < 80; ++i17286) {
        long t17288 = i17286;
        long c172870 = t17288 / 16; t17288 %= 16;
        long c172871 = t17288;
        r816[i17286] = r18[c172870 * 16 + (16 - 1 - c172871) * 1];
    }
    /* reshape [reshape] -> r817 */
    memcpy(r817, r816, sizeof(int32_t) * 80);
    /* iota [iota] -> r818 */
    for (long i17289 = 0; i17289 < 10; ++i17289) {
        long t17291 = i17289;
        long c172900 = t17291;
        r818[i17289] = (int32_t)c172900;
    }
    /* broadcast [broadcast_in_dim] -> r819 */
    for (long i17292 = 0; i17292 < 10; ++i17292) {
        long t17294 = i17292;
        long c172930 = t17294 / 1; t17294 %= 1;
        long c172931 = t17294;
        r819[i17292] = r818[c172930 * 1];
    }
    /* iota [iota] -> r820 */
    for (long i17295 = 0; i17295 < 16; ++i17295) {
        long t17297 = i17295;
        long c172960 = t17297;
        r820[i17295] = (int32_t)c172960;
    }
    /* broadcast [broadcast_in_dim] -> r821 */
    for (long i17298 = 0; i17298 < 16; ++i17298) {
        long t17300 = i17298;
        long c172990 = t17300 / 16; t17300 %= 16;
        long c172991 = t17300;
        r821[i17298] = r820[c172991 * 1];
    }
    /* add [add] -> r822 */
    for (long i17301 = 0; i17301 < 160; ++i17301) {
        long t17303 = i17301;
        long c173020 = t17303 / 16; t17303 %= 16;
        long c173021 = t17303;
        r822[i17301] = add32(r819[c173020 * 1], r821[c173021 * 1]);
    }
    /* lt [lt] -> r823 */
    for (long i17304 = 0; i17304 < 160; ++i17304) {
        r823[i17304] = r822[i17304] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r825 */
    for (long i17305 = 0; i17305 < 160; ++i17305) {
        r825[i17305] = add32(r822[i17305], r824[0]);
    }
    /* select_n [select_n] -> r826 */
    for (long i17306 = 0; i17306 < 160; ++i17306) {
        r826[i17306] = r823[i17306] == 0 ? r822[i17306] : (r825[i17306]);
    }
    /* broadcast [broadcast_in_dim] -> r827 */
    for (long i17307 = 0; i17307 < 160; ++i17307) {
        long t17309 = i17307;
        long c173080 = t17309 / 16; t17309 %= 16;
        long c173081 = t17309 / 1; t17309 %= 1;
        long c173082 = t17309;
        r827[i17307] = r826[c173080 * 16 + c173081 * 1];
    }
    /* gather [gather] -> r828 */
    for (long i17310 = 0; i17310 < 160; ++i17310) {
        long t17312 = i17310;
        long c173110 = t17312 / 160; t17312 %= 160;
        long c173111 = t17312 / 16; t17312 %= 16;
        long c173112 = t17312;
        long row17313 = c173111 * 16 + c173112 * 1;
        long s17314 = clamp_start((long)r827[row17313 + 0], 25, 1);
        r828[i17310] = r815[c173110 * 25 + s17314 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r829 */
    for (long i17315 = 0; i17315 < 160; ++i17315) {
        long t17317 = i17315;
        long c173160 = t17317 / 160; t17317 %= 160;
        long c173161 = t17317 / 160; t17317 %= 160;
        long c173162 = t17317 / 16; t17317 %= 16;
        long c173163 = t17317;
        r829[i17315] = r828[c173162 * 16 + c173163 * 1];
    }
    /* add [add] -> r830 */
    for (long i17318 = 0; i17318 < 800; ++i17318) {
        long t17320 = i17318;
        long c173190 = t17320 / 160; t17320 %= 160;
        long c173191 = t17320 / 160; t17320 %= 160;
        long c173192 = t17320 / 16; t17320 %= 16;
        long c173193 = t17320;
        r830[i17318] = add32(r817[c173190 * 16 + c173193 * 1], r829[c173192 * 16 + c173193 * 1]);
    }
    /* convert [convert_element_type] -> r831 */
    for (long i17321 = 0; i17321 < 1; ++i17321) {
        r831[i17321] = (int32_t)r48[0];
    }
    /* max [max] -> r832 */
    for (long i17322 = 0; i17322 < 800; ++i17322) {
        r832[i17322] = max32(r831[0], r830[i17322]);
    }
    /* convert [convert_element_type] -> r833 */
    for (long i17323 = 0; i17323 < 1; ++i17323) {
        r833[i17323] = (int32_t)r49[0];
    }
    /* min [min] -> r834 */
    for (long i17324 = 0; i17324 < 800; ++i17324) {
        r834[i17324] = min32(r833[0], r832[i17324]);
    }
    /* sub [sub] -> r835 */
    for (long i17325 = 0; i17325 < 800; ++i17325) {
        long t17327 = i17325;
        long c173260 = t17327 / 160; t17327 %= 160;
        long c173261 = t17327 / 160; t17327 %= 160;
        long c173262 = t17327 / 16; t17327 %= 16;
        long c173263 = t17327;
        r835[i17325] = sub32(r817[c173260 * 16 + c173263 * 1], r829[c173262 * 16 + c173263 * 1]);
    }
    /* convert [convert_element_type] -> r836 */
    for (long i17328 = 0; i17328 < 1; ++i17328) {
        r836[i17328] = (int32_t)r48[0];
    }
    /* max [max] -> r837 */
    for (long i17329 = 0; i17329 < 800; ++i17329) {
        r837[i17329] = max32(r836[0], r835[i17329]);
    }
    /* convert [convert_element_type] -> r838 */
    for (long i17330 = 0; i17330 < 1; ++i17330) {
        r838[i17330] = (int32_t)r49[0];
    }
    /* min [min] -> r839 */
    for (long i17331 = 0; i17331 < 800; ++i17331) {
        r839[i17331] = min32(r838[0], r837[i17331]);
    }
    /* abs [abs] -> r840 */
    for (long i17332 = 0; i17332 < 800; ++i17332) {
        r840[i17332] = abs32(r834[i17332]);
    }
    /* reduce_max [reduce_max] -> r841 */
    for (long i17333 = 0; i17333 < 50; ++i17333) {
        r841[i17333] = (-2147483647 - 1);
    }
    for (long i17334 = 0; i17334 < 800; ++i17334) {
        long t17336 = i17334;
        long c173350 = t17336 / 160; t17336 %= 160;
        long c173351 = t17336 / 160; t17336 %= 160;
        long c173352 = t17336 / 16; t17336 %= 16;
        long c173353 = t17336;
        r841[c173350 * 10 + c173351 * 10 + c173352 * 1] = max32(r841[c173350 * 10 + c173351 * 10 + c173352 * 1], r840[i17334]);
    }
    /* sub [sub] -> r842 */
    for (long i17337 = 0; i17337 < 50; ++i17337) {
        r842[i17337] = sub32(r841[i17337], r61[0]);
    }
    /* loop [scan] -> r864 */
    memcpy(r843, r834, sizeof(int32_t) * 800);
    memcpy(r844, r61, sizeof(int32_t) * 1);
    memcpy(r845, r39, sizeof(int32_t) * 1);
    memcpy(r846, r842, sizeof(int32_t) * 50);
    memcpy(r847, r841, sizeof(int32_t) * 50);
    for (long t17338 = 0; t17338 < 12; ++t17338) {
        /* add [add] -> r848 */
        for (long i18339 = 0; i18339 < 1; ++i18339) {
            r848[i18339] = add32(r845[0], r30[0]);
        }
        /* add [add] -> r849 */
        for (long i18340 = 0; i18340 < 50; ++i18340) {
            r849[i18340] = add32(r846[i18340], r847[i18340]);
        }
        /* shra [shift_right_arithmetic] -> r850 */
        for (long i18341 = 0; i18341 < 50; ++i18341) {
            r850[i18341] = asr32(r849[i18341], 1);
        }
        /* broadcast [broadcast_in_dim] -> r851 */
        for (long i18342 = 0; i18342 < 50; ++i18342) {
            long t18344 = i18342;
            long c183430 = t18344 / 10; t18344 %= 10;
            long c183431 = t18344 / 10; t18344 %= 10;
            long c183432 = t18344 / 1; t18344 %= 1;
            long c183433 = t18344;
            r851[i18342] = r850[c183430 * 10 + c183432 * 1];
        }
        /* sub [sub] -> r852 */
        for (long i18345 = 0; i18345 < 800; ++i18345) {
            long t18347 = i18345;
            long c183460 = t18347 / 160; t18347 %= 160;
            long c183461 = t18347 / 160; t18347 %= 160;
            long c183462 = t18347 / 16; t18347 %= 16;
            long c183463 = t18347;
            r852[i18345] = sub32(r843[c183460 * 160 + c183462 * 16 + c183463 * 1], r851[c183460 * 10 + c183462 * 1]);
        }
        /* max [max] -> r853 */
        for (long i18348 = 0; i18348 < 800; ++i18348) {
            r853[i18348] = max32(r852[i18348], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r854 */
        for (long i18349 = 0; i18349 < 50; ++i18349) {
            r854[i18349] = 0;
        }
        for (long i18350 = 0; i18350 < 800; ++i18350) {
            long t18352 = i18350;
            long c183510 = t18352 / 160; t18352 %= 160;
            long c183511 = t18352 / 160; t18352 %= 160;
            long c183512 = t18352 / 16; t18352 %= 16;
            long c183513 = t18352;
            r854[c183510 * 10 + c183511 * 10 + c183512 * 1] = add32(r854[c183510 * 10 + c183511 * 10 + c183512 * 1], r853[i18350]);
        }
        /* neg [neg] -> r855 */
        for (long i18353 = 0; i18353 < 800; ++i18353) {
            r855[i18353] = neg32(r843[i18353]);
        }
        /* broadcast [broadcast_in_dim] -> r856 */
        for (long i18354 = 0; i18354 < 50; ++i18354) {
            long t18356 = i18354;
            long c183550 = t18356 / 10; t18356 %= 10;
            long c183551 = t18356 / 10; t18356 %= 10;
            long c183552 = t18356 / 1; t18356 %= 1;
            long c183553 = t18356;
            r856[i18354] = r850[c183550 * 10 + c183552 * 1];
        }
        /* sub [sub] -> r857 */
        for (long i18357 = 0; i18357 < 800; ++i18357) {
            long t18359 = i18357;
            long c183580 = t18359 / 160; t18359 %= 160;
            long c183581 = t18359 / 160; t18359 %= 160;
            long c183582 = t18359 / 16; t18359 %= 16;
            long c183583 = t18359;
            r857[i18357] = sub32(r855[c183580 * 160 + c183582 * 16 + c183583 * 1], r856[c183580 * 10 + c183582 * 1]);
        }
        /* max [max] -> r858 */
        for (long i18360 = 0; i18360 < 800; ++i18360) {
            r858[i18360] = max32(r857[i18360], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r859 */
        for (long i18361 = 0; i18361 < 50; ++i18361) {
            r859[i18361] = 0;
        }
        for (long i18362 = 0; i18362 < 800; ++i18362) {
            long t18364 = i18362;
            long c183630 = t18364 / 160; t18364 %= 160;
            long c183631 = t18364 / 160; t18364 %= 160;
            long c183632 = t18364 / 16; t18364 %= 16;
            long c183633 = t18364;
            r859[c183630 * 10 + c183631 * 10 + c183632 * 1] = add32(r859[c183630 * 10 + c183631 * 10 + c183632 * 1], r858[i18362]);
        }
        /* add [add] -> r860 */
        for (long i18365 = 0; i18365 < 50; ++i18365) {
            r860[i18365] = add32(r854[i18365], r859[i18365]);
        }
        /* gt [gt] -> r861 */
        for (long i18366 = 0; i18366 < 50; ++i18366) {
            r861[i18366] = r860[i18366] > r844[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r862 */
        for (long i18367 = 0; i18367 < 50; ++i18367) {
            r862[i18367] = r861[i18367] == 0 ? r846[i18367] : (r850[i18367]);
        }
        /* select_n [select_n] -> r863 */
        for (long i18368 = 0; i18368 < 50; ++i18368) {
            r863[i18368] = r861[i18368] == 0 ? r850[i18368] : (r847[i18368]);
        }
        memcpy(r845, r848, sizeof(int32_t) * 1);
        memcpy(r846, r862, sizeof(int32_t) * 50);
        memcpy(r847, r863, sizeof(int32_t) * 50);
    }
    memcpy(r864, r845, sizeof(int32_t) * 1);
    memcpy(r865, r846, sizeof(int32_t) * 50);
    memcpy(r866, r847, sizeof(int32_t) * 50);
    /* abs [abs] -> r867 */
    for (long i18369 = 0; i18369 < 800; ++i18369) {
        r867[i18369] = abs32(r839[i18369]);
    }
    /* reduce_max [reduce_max] -> r868 */
    for (long i18370 = 0; i18370 < 50; ++i18370) {
        r868[i18370] = (-2147483647 - 1);
    }
    for (long i18371 = 0; i18371 < 800; ++i18371) {
        long t18373 = i18371;
        long c183720 = t18373 / 160; t18373 %= 160;
        long c183721 = t18373 / 160; t18373 %= 160;
        long c183722 = t18373 / 16; t18373 %= 16;
        long c183723 = t18373;
        r868[c183720 * 10 + c183721 * 10 + c183722 * 1] = max32(r868[c183720 * 10 + c183721 * 10 + c183722 * 1], r867[i18371]);
    }
    /* sub [sub] -> r869 */
    for (long i18374 = 0; i18374 < 50; ++i18374) {
        r869[i18374] = sub32(r868[i18374], r61[0]);
    }
    /* loop [scan] -> r891 */
    memcpy(r870, r839, sizeof(int32_t) * 800);
    memcpy(r871, r61, sizeof(int32_t) * 1);
    memcpy(r872, r39, sizeof(int32_t) * 1);
    memcpy(r873, r869, sizeof(int32_t) * 50);
    memcpy(r874, r868, sizeof(int32_t) * 50);
    for (long t18375 = 0; t18375 < 12; ++t18375) {
        /* add [add] -> r875 */
        for (long i19376 = 0; i19376 < 1; ++i19376) {
            r875[i19376] = add32(r872[0], r30[0]);
        }
        /* add [add] -> r876 */
        for (long i19377 = 0; i19377 < 50; ++i19377) {
            r876[i19377] = add32(r873[i19377], r874[i19377]);
        }
        /* shra [shift_right_arithmetic] -> r877 */
        for (long i19378 = 0; i19378 < 50; ++i19378) {
            r877[i19378] = asr32(r876[i19378], 1);
        }
        /* broadcast [broadcast_in_dim] -> r878 */
        for (long i19379 = 0; i19379 < 50; ++i19379) {
            long t19381 = i19379;
            long c193800 = t19381 / 10; t19381 %= 10;
            long c193801 = t19381 / 10; t19381 %= 10;
            long c193802 = t19381 / 1; t19381 %= 1;
            long c193803 = t19381;
            r878[i19379] = r877[c193800 * 10 + c193802 * 1];
        }
        /* sub [sub] -> r879 */
        for (long i19382 = 0; i19382 < 800; ++i19382) {
            long t19384 = i19382;
            long c193830 = t19384 / 160; t19384 %= 160;
            long c193831 = t19384 / 160; t19384 %= 160;
            long c193832 = t19384 / 16; t19384 %= 16;
            long c193833 = t19384;
            r879[i19382] = sub32(r870[c193830 * 160 + c193832 * 16 + c193833 * 1], r878[c193830 * 10 + c193832 * 1]);
        }
        /* max [max] -> r880 */
        for (long i19385 = 0; i19385 < 800; ++i19385) {
            r880[i19385] = max32(r879[i19385], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r881 */
        for (long i19386 = 0; i19386 < 50; ++i19386) {
            r881[i19386] = 0;
        }
        for (long i19387 = 0; i19387 < 800; ++i19387) {
            long t19389 = i19387;
            long c193880 = t19389 / 160; t19389 %= 160;
            long c193881 = t19389 / 160; t19389 %= 160;
            long c193882 = t19389 / 16; t19389 %= 16;
            long c193883 = t19389;
            r881[c193880 * 10 + c193881 * 10 + c193882 * 1] = add32(r881[c193880 * 10 + c193881 * 10 + c193882 * 1], r880[i19387]);
        }
        /* neg [neg] -> r882 */
        for (long i19390 = 0; i19390 < 800; ++i19390) {
            r882[i19390] = neg32(r870[i19390]);
        }
        /* broadcast [broadcast_in_dim] -> r883 */
        for (long i19391 = 0; i19391 < 50; ++i19391) {
            long t19393 = i19391;
            long c193920 = t19393 / 10; t19393 %= 10;
            long c193921 = t19393 / 10; t19393 %= 10;
            long c193922 = t19393 / 1; t19393 %= 1;
            long c193923 = t19393;
            r883[i19391] = r877[c193920 * 10 + c193922 * 1];
        }
        /* sub [sub] -> r884 */
        for (long i19394 = 0; i19394 < 800; ++i19394) {
            long t19396 = i19394;
            long c193950 = t19396 / 160; t19396 %= 160;
            long c193951 = t19396 / 160; t19396 %= 160;
            long c193952 = t19396 / 16; t19396 %= 16;
            long c193953 = t19396;
            r884[i19394] = sub32(r882[c193950 * 160 + c193952 * 16 + c193953 * 1], r883[c193950 * 10 + c193952 * 1]);
        }
        /* max [max] -> r885 */
        for (long i19397 = 0; i19397 < 800; ++i19397) {
            r885[i19397] = max32(r884[i19397], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r886 */
        for (long i19398 = 0; i19398 < 50; ++i19398) {
            r886[i19398] = 0;
        }
        for (long i19399 = 0; i19399 < 800; ++i19399) {
            long t19401 = i19399;
            long c194000 = t19401 / 160; t19401 %= 160;
            long c194001 = t19401 / 160; t19401 %= 160;
            long c194002 = t19401 / 16; t19401 %= 16;
            long c194003 = t19401;
            r886[c194000 * 10 + c194001 * 10 + c194002 * 1] = add32(r886[c194000 * 10 + c194001 * 10 + c194002 * 1], r885[i19399]);
        }
        /* add [add] -> r887 */
        for (long i19402 = 0; i19402 < 50; ++i19402) {
            r887[i19402] = add32(r881[i19402], r886[i19402]);
        }
        /* gt [gt] -> r888 */
        for (long i19403 = 0; i19403 < 50; ++i19403) {
            r888[i19403] = r887[i19403] > r871[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r889 */
        for (long i19404 = 0; i19404 < 50; ++i19404) {
            r889[i19404] = r888[i19404] == 0 ? r873[i19404] : (r877[i19404]);
        }
        /* select_n [select_n] -> r890 */
        for (long i19405 = 0; i19405 < 50; ++i19405) {
            r890[i19405] = r888[i19405] == 0 ? r877[i19405] : (r874[i19405]);
        }
        memcpy(r872, r875, sizeof(int32_t) * 1);
        memcpy(r873, r889, sizeof(int32_t) * 50);
        memcpy(r874, r890, sizeof(int32_t) * 50);
    }
    memcpy(r891, r872, sizeof(int32_t) * 1);
    memcpy(r892, r873, sizeof(int32_t) * 50);
    memcpy(r893, r874, sizeof(int32_t) * 50);
    /* sub [sub] -> r894 */
    for (long i19406 = 0; i19406 < 50; ++i19406) {
        r894[i19406] = sub32(r866[i19406], r893[i19406]);
    }
    /* transpose [transpose] -> r895 */
    for (long i19407 = 0; i19407 < 50; ++i19407) {
        long t19409 = i19407;
        long c194080 = t19409 / 50; t19409 %= 50;
        long c194081 = t19409 / 10; t19409 %= 10;
        long c194082 = t19409;
        r895[i19407] = r894[c194080 * 10 + c194081 * 10 + c194082 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r896 */
    for (long i19410 = 0; i19410 < 1; ++i19410) {
        long t19412 = i19410;
        long c194110 = t19412 / 1; t19412 %= 1;
        long c194111 = t19412;
        r896[i19410] = r813[0];
    }
    /* max [max] -> r897 */
    for (long i19413 = 0; i19413 < 50; ++i19413) {
        r897[i19413] = max32(r895[i19413], r39[0]);
    }
    /* iota [iota] -> r898 */
    for (long i19414 = 0; i19414 < 50; ++i19414) {
        long t19416 = i19414;
        long c194150 = t19416 / 50; t19416 %= 50;
        long c194151 = t19416 / 10; t19416 %= 10;
        long c194152 = t19416;
        r898[i19414] = (int32_t)c194152;
    }
    /* broadcast [broadcast_in_dim] -> r899 */
    for (long i19417 = 0; i19417 < 1; ++i19417) {
        long t19419 = i19417;
        long c194180 = t19419 / 1; t19419 %= 1;
        long c194181 = t19419 / 1; t19419 %= 1;
        long c194182 = t19419;
        r899[i19417] = r896[0];
    }
    /* lt [lt] -> r900 */
    for (long i19420 = 0; i19420 < 50; ++i19420) {
        long t19422 = i19420;
        long c194210 = t19422 / 50; t19422 %= 50;
        long c194211 = t19422 / 10; t19422 %= 10;
        long c194212 = t19422;
        r900[i19420] = r898[c194211 * 10 + c194212 * 1] < r899[0] ? 1 : 0;
    }
    /* convert [convert_element_type] -> r901 */
    for (long i19423 = 0; i19423 < 1; ++i19423) {
        r901[i19423] = (int32_t)r39[0];
    }
    /* broadcast [broadcast_in_dim] -> r902 */
    for (long i19424 = 0; i19424 < 50; ++i19424) {
        long t19426 = i19424;
        long c194250 = t19426 / 50; t19426 %= 50;
        long c194251 = t19426 / 10; t19426 %= 10;
        long c194252 = t19426;
        r902[i19424] = r901[0];
    }
    /* select_n [select_n] -> r903 */
    for (long i19427 = 0; i19427 < 50; ++i19427) {
        r903[i19427] = r900[i19427] == 0 ? r902[i19427] : (r897[i19427]);
    }
    /* reduce_sum [reduce_sum] -> r904 */
    for (long i19428 = 0; i19428 < 5; ++i19428) {
        r904[i19428] = 0;
    }
    for (long i19429 = 0; i19429 < 50; ++i19429) {
        long t19431 = i19429;
        long c194300 = t19431 / 50; t19431 %= 50;
        long c194301 = t19431 / 10; t19431 %= 10;
        long c194302 = t19431;
        r904[c194300 * 5 + c194301 * 1] = add32(r904[c194300 * 5 + c194301 * 1], r903[i19429]);
    }
    /* shl [shift_left] -> r906 */
    for (long i19432 = 0; i19432 < 5; ++i19432) {
        r906[i19432] = shl32(r904[i19432], 4);
    }
    /* lt [lt] -> r907 */
    for (long i19433 = 0; i19433 < 1; ++i19433) {
        r907[i19433] = r813[i19433] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r908 */
    for (long i19434 = 0; i19434 < 1; ++i19434) {
        r908[i19434] = add32(r813[i19434], r824[0]);
    }
    /* select_n [select_n] -> r909 */
    for (long i19435 = 0; i19435 < 1; ++i19435) {
        r909[i19435] = r907[i19435] == 0 ? r813[i19435] : (r908[i19435]);
    }
    /* broadcast [broadcast_in_dim] -> r910 */
    for (long i19436 = 0; i19436 < 1; ++i19436) {
        long t19438 = i19436;
        long c194370 = t19438 / 1; t19438 %= 1;
        long c194371 = t19438;
        r910[i19436] = r909[0];
    }
    /* gather [gather] -> r911 */
    for (long i19439 = 0; i19439 < 15; ++i19439) {
        long t19441 = i19439;
        long c194400 = t19441 / 15; t19441 %= 15;
        long c194401 = t19441;
        long row19442 = c194400 * 1;
        long s19443 = clamp_start((long)r910[row19442 + 0], 25, 15);
        r911[i19439] = r814[c194400 * 25 + (s19443 + c194401) * 1];
    }
    /* add [add] -> r912 */
    for (long i19444 = 0; i19444 < 1; ++i19444) {
        r912[i19444] = add32(r10[i19444], r813[i19444]);
    }
    /* and [and] -> r913 */
    for (long i19445 = 0; i19445 < 1; ++i19445) {
        r913[i19445] = r10[i19445] & r30[0];
    }
    /* slice [slice] -> r914 */
    for (long i19446 = 0; i19446 < 15; ++i19446) {
        long t19448 = i19446;
        long c194470 = t19448 / 15; t19448 %= 15;
        long c194471 = t19448;
        r914[i19446] = r814[(0 + c194470 * 1) * 25 + (10 + c194471 * 1) * 1];
    }
    /* shl [shift_left] -> r915 */
    for (long i19449 = 0; i19449 < 15; ++i19449) {
        r915[i19449] = shl32(r914[i19449], 1);
    }
    /* convert [convert_element_type] -> r916 */
    for (long i19450 = 0; i19450 < 1; ++i19450) {
        r916[i19450] = (int32_t)r39[0];
    }
    /* pad [pad] -> r917 */
    for (long i19451 = 0; i19451 < 16; ++i19451) {
        r917[i19451] = r916[0];
    }
    for (long i19452 = 0; i19452 < 15; ++i19452) {
        long t19454 = i19452;
        long c194530 = t19454 / 15; t19454 %= 15;
        long c194531 = t19454;
        long d19455 = 0 + c194530 * 1;
        long d19456 = 0 + c194531 * 1;
        if (d19455 >= 0 && d19455 < 1 && d19456 >= 0 && d19456 < 16) r917[d19455 * 16 + d19456 * 1] = r915[i19452];
    }
    /* iota [iota] -> r918 */
    for (long i19457 = 0; i19457 < 5; ++i19457) {
        long t19459 = i19457;
        long c194580 = t19459;
        r918[i19457] = (int32_t)c194580;
    }
    /* shl [shift_left] -> r919 */
    for (long i19460 = 0; i19460 < 5; ++i19460) {
        r919[i19460] = shl32(r918[i19460], 1);
    }
    /* broadcast [broadcast_in_dim] -> r920 */
    for (long i19461 = 0; i19461 < 5; ++i19461) {
        long t19463 = i19461;
        long c194620 = t19463 / 1; t19463 %= 1;
        long c194621 = t19463;
        r920[i19461] = r919[c194620 * 1];
    }
    /* iota [iota] -> r921 */
    for (long i19464 = 0; i19464 < 6; ++i19464) {
        long t19466 = i19464;
        long c194650 = t19466;
        r921[i19464] = (int32_t)c194650;
    }
    /* broadcast [broadcast_in_dim] -> r922 */
    for (long i19467 = 0; i19467 < 6; ++i19467) {
        long t19469 = i19467;
        long c194680 = t19469 / 6; t19469 %= 6;
        long c194681 = t19469;
        r922[i19467] = r921[c194681 * 1];
    }
    /* add [add] -> r923 */
    for (long i19470 = 0; i19470 < 30; ++i19470) {
        long t19472 = i19470;
        long c194710 = t19472 / 6; t19472 %= 6;
        long c194711 = t19472;
        r923[i19470] = add32(r920[c194710 * 1], r922[c194711 * 1]);
    }
    /* broadcast [broadcast_in_dim] -> r924 */
    for (long i19473 = 0; i19473 < 30; ++i19473) {
        long t19475 = i19473;
        long c194740 = t19475 / 30; t19475 %= 30;
        long c194741 = t19475 / 6; t19475 %= 6;
        long c194742 = t19475;
        r924[i19473] = r923[c194741 * 6 + c194742 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r925 */
    for (long i19476 = 0; i19476 < 1; ++i19476) {
        long t19478 = i19476;
        long c194770 = t19478 / 1; t19478 %= 1;
        long c194771 = t19478 / 1; t19478 %= 1;
        long c194772 = t19478;
        r925[i19476] = r913[0];
    }
    /* add [add] -> r926 */
    for (long i19479 = 0; i19479 < 30; ++i19479) {
        long t19481 = i19479;
        long c194800 = t19481 / 30; t19481 %= 30;
        long c194801 = t19481 / 6; t19481 %= 6;
        long c194802 = t19481;
        r926[i19479] = add32(r925[0], r924[c194801 * 6 + c194802 * 1]);
    }
    /* lt [lt] -> r927 */
    for (long i19482 = 0; i19482 < 30; ++i19482) {
        r927[i19482] = r926[i19482] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r929 */
    for (long i19483 = 0; i19483 < 30; ++i19483) {
        r929[i19483] = add32(r926[i19483], r928[0]);
    }
    /* select_n [select_n] -> r930 */
    for (long i19484 = 0; i19484 < 30; ++i19484) {
        r930[i19484] = r927[i19484] == 0 ? r926[i19484] : (r929[i19484]);
    }
    /* broadcast [broadcast_in_dim] -> r931 */
    for (long i19485 = 0; i19485 < 30; ++i19485) {
        long t19487 = i19485;
        long c194860 = t19487 / 30; t19487 %= 30;
        long c194861 = t19487 / 6; t19487 %= 6;
        long c194862 = t19487 / 1; t19487 %= 1;
        long c194863 = t19487;
        r931[i19485] = r930[c194861 * 6 + c194862 * 1];
    }
    /* gather [gather] -> r932 */
    for (long i19488 = 0; i19488 < 30; ++i19488) {
        long t19490 = i19488;
        long c194890 = t19490 / 30; t19490 %= 30;
        long c194891 = t19490 / 6; t19490 %= 6;
        long c194892 = t19490;
        long row19491 = c194890 * 30 + c194891 * 6 + c194892 * 1;
        long s19492 = clamp_start((long)r931[row19491 + 0], 16, 1);
        r932[i19488] = r917[c194890 * 16 + s19492 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r933 */
    for (long i19493 = 0; i19493 < 6; ++i19493) {
        long t19495 = i19493;
        long c194940 = t19495 / 6; t19495 %= 6;
        long c194941 = t19495 / 6; t19495 %= 6;
        long c194942 = t19495;
        r933[i19493] = r19[c194942 * 1];
    }
    /* add [add] -> r934 */
    for (long i19496 = 0; i19496 < 30; ++i19496) {
        long t19498 = i19496;
        long c194970 = t19498 / 30; t19498 %= 30;
        long c194971 = t19498 / 6; t19498 %= 6;
        long c194972 = t19498;
        r934[i19496] = add32(r933[c194972 * 1], r932[c194971 * 6 + c194972 * 1]);
    }
    /* convert [convert_element_type] -> r935 */
    for (long i19499 = 0; i19499 < 1; ++i19499) {
        r935[i19499] = (int32_t)r48[0];
    }
    /* max [max] -> r936 */
    for (long i19500 = 0; i19500 < 30; ++i19500) {
        r936[i19500] = max32(r935[0], r934[i19500]);
    }
    /* convert [convert_element_type] -> r937 */
    for (long i19501 = 0; i19501 < 1; ++i19501) {
        r937[i19501] = (int32_t)r49[0];
    }
    /* min [min] -> r938 */
    for (long i19502 = 0; i19502 < 30; ++i19502) {
        r938[i19502] = min32(r937[0], r936[i19502]);
    }
    /* broadcast [broadcast_in_dim] -> r939 */
    for (long i19503 = 0; i19503 < 6; ++i19503) {
        long t19505 = i19503;
        long c195040 = t19505 / 6; t19505 %= 6;
        long c195041 = t19505 / 6; t19505 %= 6;
        long c195042 = t19505;
        r939[i19503] = r19[c195042 * 1];
    }
    /* sub [sub] -> r940 */
    for (long i19506 = 0; i19506 < 30; ++i19506) {
        long t19508 = i19506;
        long c195070 = t19508 / 30; t19508 %= 30;
        long c195071 = t19508 / 6; t19508 %= 6;
        long c195072 = t19508;
        r940[i19506] = sub32(r939[c195072 * 1], r932[c195071 * 6 + c195072 * 1]);
    }
    /* convert [convert_element_type] -> r941 */
    for (long i19509 = 0; i19509 < 1; ++i19509) {
        r941[i19509] = (int32_t)r48[0];
    }
    /* max [max] -> r942 */
    for (long i19510 = 0; i19510 < 30; ++i19510) {
        r942[i19510] = max32(r941[0], r940[i19510]);
    }
    /* convert [convert_element_type] -> r943 */
    for (long i19511 = 0; i19511 < 1; ++i19511) {
        r943[i19511] = (int32_t)r49[0];
    }
    /* min [min] -> r944 */
    for (long i19512 = 0; i19512 < 30; ++i19512) {
        r944[i19512] = min32(r943[0], r942[i19512]);
    }
    /* abs [abs] -> r945 */
    for (long i19513 = 0; i19513 < 30; ++i19513) {
        r945[i19513] = abs32(r938[i19513]);
    }
    /* reduce_max [reduce_max] -> r946 */
    for (long i19514 = 0; i19514 < 5; ++i19514) {
        r946[i19514] = (-2147483647 - 1);
    }
    for (long i19515 = 0; i19515 < 30; ++i19515) {
        long t19517 = i19515;
        long c195160 = t19517 / 30; t19517 %= 30;
        long c195161 = t19517 / 6; t19517 %= 6;
        long c195162 = t19517;
        r946[c195160 * 5 + c195161 * 1] = max32(r946[c195160 * 5 + c195161 * 1], r945[i19515]);
    }
    /* sub [sub] -> r947 */
    for (long i19518 = 0; i19518 < 5; ++i19518) {
        r947[i19518] = sub32(r946[i19518], r61[0]);
    }
    /* loop [scan] -> r969 */
    memcpy(r948, r938, sizeof(int32_t) * 30);
    memcpy(r949, r61, sizeof(int32_t) * 1);
    memcpy(r950, r39, sizeof(int32_t) * 1);
    memcpy(r951, r947, sizeof(int32_t) * 5);
    memcpy(r952, r946, sizeof(int32_t) * 5);
    for (long t19519 = 0; t19519 < 12; ++t19519) {
        /* add [add] -> r953 */
        for (long i20520 = 0; i20520 < 1; ++i20520) {
            r953[i20520] = add32(r950[0], r30[0]);
        }
        /* add [add] -> r954 */
        for (long i20521 = 0; i20521 < 5; ++i20521) {
            r954[i20521] = add32(r951[i20521], r952[i20521]);
        }
        /* shra [shift_right_arithmetic] -> r955 */
        for (long i20522 = 0; i20522 < 5; ++i20522) {
            r955[i20522] = asr32(r954[i20522], 1);
        }
        /* broadcast [broadcast_in_dim] -> r956 */
        for (long i20523 = 0; i20523 < 5; ++i20523) {
            long t20525 = i20523;
            long c205240 = t20525 / 5; t20525 %= 5;
            long c205241 = t20525 / 1; t20525 %= 1;
            long c205242 = t20525;
            r956[i20523] = r955[c205241 * 1];
        }
        /* sub [sub] -> r957 */
        for (long i20526 = 0; i20526 < 30; ++i20526) {
            long t20528 = i20526;
            long c205270 = t20528 / 30; t20528 %= 30;
            long c205271 = t20528 / 6; t20528 %= 6;
            long c205272 = t20528;
            r957[i20526] = sub32(r948[c205271 * 6 + c205272 * 1], r956[c205271 * 1]);
        }
        /* max [max] -> r958 */
        for (long i20529 = 0; i20529 < 30; ++i20529) {
            r958[i20529] = max32(r957[i20529], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r959 */
        for (long i20530 = 0; i20530 < 5; ++i20530) {
            r959[i20530] = 0;
        }
        for (long i20531 = 0; i20531 < 30; ++i20531) {
            long t20533 = i20531;
            long c205320 = t20533 / 30; t20533 %= 30;
            long c205321 = t20533 / 6; t20533 %= 6;
            long c205322 = t20533;
            r959[c205320 * 5 + c205321 * 1] = add32(r959[c205320 * 5 + c205321 * 1], r958[i20531]);
        }
        /* neg [neg] -> r960 */
        for (long i20534 = 0; i20534 < 30; ++i20534) {
            r960[i20534] = neg32(r948[i20534]);
        }
        /* broadcast [broadcast_in_dim] -> r961 */
        for (long i20535 = 0; i20535 < 5; ++i20535) {
            long t20537 = i20535;
            long c205360 = t20537 / 5; t20537 %= 5;
            long c205361 = t20537 / 1; t20537 %= 1;
            long c205362 = t20537;
            r961[i20535] = r955[c205361 * 1];
        }
        /* sub [sub] -> r962 */
        for (long i20538 = 0; i20538 < 30; ++i20538) {
            long t20540 = i20538;
            long c205390 = t20540 / 30; t20540 %= 30;
            long c205391 = t20540 / 6; t20540 %= 6;
            long c205392 = t20540;
            r962[i20538] = sub32(r960[c205391 * 6 + c205392 * 1], r961[c205391 * 1]);
        }
        /* max [max] -> r963 */
        for (long i20541 = 0; i20541 < 30; ++i20541) {
            r963[i20541] = max32(r962[i20541], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r964 */
        for (long i20542 = 0; i20542 < 5; ++i20542) {
            r964[i20542] = 0;
        }
        for (long i20543 = 0; i20543 < 30; ++i20543) {
            long t20545 = i20543;
            long c205440 = t20545 / 30; t20545 %= 30;
            long c205441 = t20545 / 6; t20545 %= 6;
            long c205442 = t20545;
            r964[c205440 * 5 + c205441 * 1] = add32(r964[c205440 * 5 + c205441 * 1], r963[i20543]);
        }
        /* add [add] -> r965 */
        for (long i20546 = 0; i20546 < 5; ++i20546) {
            r965[i20546] = add32(r959[i20546], r964[i20546]);
        }
        /* gt [gt] -> r966 */
        for (long i20547 = 0; i20547 < 5; ++i20547) {
            r966[i20547] = r965[i20547] > r949[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r967 */
        for (long i20548 = 0; i20548 < 5; ++i20548) {
            r967[i20548] = r966[i20548] == 0 ? r951[i20548] : (r955[i20548]);
        }
        /* select_n [select_n] -> r968 */
        for (long i20549 = 0; i20549 < 5; ++i20549) {
            r968[i20549] = r966[i20549] == 0 ? r955[i20549] : (r952[i20549]);
        }
        memcpy(r950, r953, sizeof(int32_t) * 1);
        memcpy(r951, r967, sizeof(int32_t) * 5);
        memcpy(r952, r968, sizeof(int32_t) * 5);
    }
    memcpy(r969, r950, sizeof(int32_t) * 1);
    memcpy(r970, r951, sizeof(int32_t) * 5);
    memcpy(r971, r952, sizeof(int32_t) * 5);
    /* abs [abs] -> r972 */
    for (long i20550 = 0; i20550 < 30; ++i20550) {
        r972[i20550] = abs32(r944[i20550]);
    }
    /* reduce_max [reduce_max] -> r973 */
    for (long i20551 = 0; i20551 < 5; ++i20551) {
        r973[i20551] = (-2147483647 - 1);
    }
    for (long i20552 = 0; i20552 < 30; ++i20552) {
        long t20554 = i20552;
        long c205530 = t20554 / 30; t20554 %= 30;
        long c205531 = t20554 / 6; t20554 %= 6;
        long c205532 = t20554;
        r973[c205530 * 5 + c205531 * 1] = max32(r973[c205530 * 5 + c205531 * 1], r972[i20552]);
    }
    /* sub [sub] -> r974 */
    for (long i20555 = 0; i20555 < 5; ++i20555) {
        r974[i20555] = sub32(r973[i20555], r61[0]);
    }
    /* loop [scan] -> r996 */
    memcpy(r975, r944, sizeof(int32_t) * 30);
    memcpy(r976, r61, sizeof(int32_t) * 1);
    memcpy(r977, r39, sizeof(int32_t) * 1);
    memcpy(r978, r974, sizeof(int32_t) * 5);
    memcpy(r979, r973, sizeof(int32_t) * 5);
    for (long t20556 = 0; t20556 < 12; ++t20556) {
        /* add [add] -> r980 */
        for (long i21557 = 0; i21557 < 1; ++i21557) {
            r980[i21557] = add32(r977[0], r30[0]);
        }
        /* add [add] -> r981 */
        for (long i21558 = 0; i21558 < 5; ++i21558) {
            r981[i21558] = add32(r978[i21558], r979[i21558]);
        }
        /* shra [shift_right_arithmetic] -> r982 */
        for (long i21559 = 0; i21559 < 5; ++i21559) {
            r982[i21559] = asr32(r981[i21559], 1);
        }
        /* broadcast [broadcast_in_dim] -> r983 */
        for (long i21560 = 0; i21560 < 5; ++i21560) {
            long t21562 = i21560;
            long c215610 = t21562 / 5; t21562 %= 5;
            long c215611 = t21562 / 1; t21562 %= 1;
            long c215612 = t21562;
            r983[i21560] = r982[c215611 * 1];
        }
        /* sub [sub] -> r984 */
        for (long i21563 = 0; i21563 < 30; ++i21563) {
            long t21565 = i21563;
            long c215640 = t21565 / 30; t21565 %= 30;
            long c215641 = t21565 / 6; t21565 %= 6;
            long c215642 = t21565;
            r984[i21563] = sub32(r975[c215641 * 6 + c215642 * 1], r983[c215641 * 1]);
        }
        /* max [max] -> r985 */
        for (long i21566 = 0; i21566 < 30; ++i21566) {
            r985[i21566] = max32(r984[i21566], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r986 */
        for (long i21567 = 0; i21567 < 5; ++i21567) {
            r986[i21567] = 0;
        }
        for (long i21568 = 0; i21568 < 30; ++i21568) {
            long t21570 = i21568;
            long c215690 = t21570 / 30; t21570 %= 30;
            long c215691 = t21570 / 6; t21570 %= 6;
            long c215692 = t21570;
            r986[c215690 * 5 + c215691 * 1] = add32(r986[c215690 * 5 + c215691 * 1], r985[i21568]);
        }
        /* neg [neg] -> r987 */
        for (long i21571 = 0; i21571 < 30; ++i21571) {
            r987[i21571] = neg32(r975[i21571]);
        }
        /* broadcast [broadcast_in_dim] -> r988 */
        for (long i21572 = 0; i21572 < 5; ++i21572) {
            long t21574 = i21572;
            long c215730 = t21574 / 5; t21574 %= 5;
            long c215731 = t21574 / 1; t21574 %= 1;
            long c215732 = t21574;
            r988[i21572] = r982[c215731 * 1];
        }
        /* sub [sub] -> r989 */
        for (long i21575 = 0; i21575 < 30; ++i21575) {
            long t21577 = i21575;
            long c215760 = t21577 / 30; t21577 %= 30;
            long c215761 = t21577 / 6; t21577 %= 6;
            long c215762 = t21577;
            r989[i21575] = sub32(r987[c215761 * 6 + c215762 * 1], r988[c215761 * 1]);
        }
        /* max [max] -> r990 */
        for (long i21578 = 0; i21578 < 30; ++i21578) {
            r990[i21578] = max32(r989[i21578], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r991 */
        for (long i21579 = 0; i21579 < 5; ++i21579) {
            r991[i21579] = 0;
        }
        for (long i21580 = 0; i21580 < 30; ++i21580) {
            long t21582 = i21580;
            long c215810 = t21582 / 30; t21582 %= 30;
            long c215811 = t21582 / 6; t21582 %= 6;
            long c215812 = t21582;
            r991[c215810 * 5 + c215811 * 1] = add32(r991[c215810 * 5 + c215811 * 1], r990[i21580]);
        }
        /* add [add] -> r992 */
        for (long i21583 = 0; i21583 < 5; ++i21583) {
            r992[i21583] = add32(r986[i21583], r991[i21583]);
        }
        /* gt [gt] -> r993 */
        for (long i21584 = 0; i21584 < 5; ++i21584) {
            r993[i21584] = r992[i21584] > r976[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r994 */
        for (long i21585 = 0; i21585 < 5; ++i21585) {
            r994[i21585] = r993[i21585] == 0 ? r978[i21585] : (r982[i21585]);
        }
        /* select_n [select_n] -> r995 */
        for (long i21586 = 0; i21586 < 5; ++i21586) {
            r995[i21586] = r993[i21586] == 0 ? r982[i21586] : (r979[i21586]);
        }
        memcpy(r977, r980, sizeof(int32_t) * 1);
        memcpy(r978, r994, sizeof(int32_t) * 5);
        memcpy(r979, r995, sizeof(int32_t) * 5);
    }
    memcpy(r996, r977, sizeof(int32_t) * 1);
    memcpy(r997, r978, sizeof(int32_t) * 5);
    memcpy(r998, r979, sizeof(int32_t) * 5);
    /* sub [sub] -> r999 */
    for (long i21587 = 0; i21587 < 5; ++i21587) {
        r999[i21587] = sub32(r971[i21587], r998[i21587]);
    }
    /* shra [shift_right_arithmetic] -> r1000 */
    for (long i21588 = 0; i21588 < 5; ++i21588) {
        r1000[i21588] = asr32(r999[i21588], 1);
    }
    /* convert [convert_element_type] -> r1001 */
    for (long i21589 = 0; i21589 < 1; ++i21589) {
        r1001[i21589] = (int32_t)r220[0];
    }
    /* max [max] -> r1002 */
    for (long i21590 = 0; i21590 < 5; ++i21590) {
        r1002[i21590] = max32(r1001[0], r1000[i21590]);
    }
    /* convert [convert_element_type] -> r1003 */
    for (long i21591 = 0; i21591 < 1; ++i21591) {
        r1003[i21591] = (int32_t)r221[0];
    }
    /* min [min] -> r1004 */
    for (long i21592 = 0; i21592 < 5; ++i21592) {
        r1004[i21592] = min32(r1003[0], r1002[i21592]);
    }
    /* sub [sub] -> r1005 */
    for (long i21593 = 0; i21593 < 1; ++i21593) {
        r1005[i21593] = sub32(r813[i21593], r913[i21593]);
    }
    /* add [add] -> r1006 */
    for (long i21594 = 0; i21594 < 1; ++i21594) {
        r1006[i21594] = add32(r1005[i21594], r30[0]);
    }
    /* max [max] -> r1007 */
    for (long i21595 = 0; i21595 < 1; ++i21595) {
        r1007[i21595] = max32(r1006[i21595], r39[0]);
    }
    /* shra [shift_right_arithmetic] -> r1008 */
    for (long i21596 = 0; i21596 < 1; ++i21596) {
        r1008[i21596] = asr32(r1007[i21596], 1);
    }
    /* concat [concatenate] -> r1009 */
    for (long i21597 = 0; i21597 < 15; ++i21597) {
        long t21599 = i21597;
        long c215980 = t21599 / 15; t21599 %= 15;
        long c215981 = t21599;
        r1009[c215980 * 20 + (c215981 + 0) * 1] = r5[i21597];
    }
    for (long i21600 = 0; i21600 < 5; ++i21600) {
        long t21602 = i21600;
        long c216010 = t21602 / 5; t21602 %= 5;
        long c216011 = t21602;
        r1009[c216010 * 20 + (c216011 + 15) * 1] = r1004[i21600];
    }
    /* shl [shift_left] -> r1010 */
    for (long i21603 = 0; i21603 < 20; ++i21603) {
        r1010[i21603] = shl32(r1009[i21603], 1);
    }
    /* rev [rev] -> r1011 */
    for (long i21604 = 0; i21604 < 80; ++i21604) {
        long t21606 = i21604;
        long c216050 = t21606 / 16; t21606 %= 16;
        long c216051 = t21606;
        r1011[i21604] = r18[c216050 * 16 + (16 - 1 - c216051) * 1];
    }
    /* reshape [reshape] -> r1012 */
    memcpy(r1012, r1011, sizeof(int32_t) * 80);
    /* iota [iota] -> r1013 */
    for (long i21607 = 0; i21607 < 5; ++i21607) {
        long t21609 = i21607;
        long c216080 = t21609;
        r1013[i21607] = (int32_t)c216080;
    }
    /* broadcast [broadcast_in_dim] -> r1014 */
    for (long i21610 = 0; i21610 < 5; ++i21610) {
        long t21612 = i21610;
        long c216110 = t21612 / 1; t21612 %= 1;
        long c216111 = t21612;
        r1014[i21610] = r1013[c216110 * 1];
    }
    /* iota [iota] -> r1015 */
    for (long i21613 = 0; i21613 < 16; ++i21613) {
        long t21615 = i21613;
        long c216140 = t21615;
        r1015[i21613] = (int32_t)c216140;
    }
    /* broadcast [broadcast_in_dim] -> r1016 */
    for (long i21616 = 0; i21616 < 16; ++i21616) {
        long t21618 = i21616;
        long c216170 = t21618 / 16; t21618 %= 16;
        long c216171 = t21618;
        r1016[i21616] = r1015[c216171 * 1];
    }
    /* add [add] -> r1017 */
    for (long i21619 = 0; i21619 < 80; ++i21619) {
        long t21621 = i21619;
        long c216200 = t21621 / 16; t21621 %= 16;
        long c216201 = t21621;
        r1017[i21619] = add32(r1014[c216200 * 1], r1016[c216201 * 1]);
    }
    /* lt [lt] -> r1018 */
    for (long i21622 = 0; i21622 < 80; ++i21622) {
        r1018[i21622] = r1017[i21622] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r1020 */
    for (long i21623 = 0; i21623 < 80; ++i21623) {
        r1020[i21623] = add32(r1017[i21623], r1019[0]);
    }
    /* select_n [select_n] -> r1021 */
    for (long i21624 = 0; i21624 < 80; ++i21624) {
        r1021[i21624] = r1018[i21624] == 0 ? r1017[i21624] : (r1020[i21624]);
    }
    /* broadcast [broadcast_in_dim] -> r1022 */
    for (long i21625 = 0; i21625 < 80; ++i21625) {
        long t21627 = i21625;
        long c216260 = t21627 / 16; t21627 %= 16;
        long c216261 = t21627 / 1; t21627 %= 1;
        long c216262 = t21627;
        r1022[i21625] = r1021[c216260 * 16 + c216261 * 1];
    }
    /* gather [gather] -> r1023 */
    for (long i21628 = 0; i21628 < 80; ++i21628) {
        long t21630 = i21628;
        long c216290 = t21630 / 80; t21630 %= 80;
        long c216291 = t21630 / 16; t21630 %= 16;
        long c216292 = t21630;
        long row21631 = c216291 * 16 + c216292 * 1;
        long s21632 = clamp_start((long)r1022[row21631 + 0], 20, 1);
        r1023[i21628] = r1010[c216290 * 20 + s21632 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r1024 */
    for (long i21633 = 0; i21633 < 80; ++i21633) {
        long t21635 = i21633;
        long c216340 = t21635 / 80; t21635 %= 80;
        long c216341 = t21635 / 80; t21635 %= 80;
        long c216342 = t21635 / 16; t21635 %= 16;
        long c216343 = t21635;
        r1024[i21633] = r1023[c216342 * 16 + c216343 * 1];
    }
    /* add [add] -> r1025 */
    for (long i21636 = 0; i21636 < 400; ++i21636) {
        long t21638 = i21636;
        long c216370 = t21638 / 80; t21638 %= 80;
        long c216371 = t21638 / 80; t21638 %= 80;
        long c216372 = t21638 / 16; t21638 %= 16;
        long c216373 = t21638;
        r1025[i21636] = add32(r1012[c216370 * 16 + c216373 * 1], r1024[c216372 * 16 + c216373 * 1]);
    }
    /* convert [convert_element_type] -> r1026 */
    for (long i21639 = 0; i21639 < 1; ++i21639) {
        r1026[i21639] = (int32_t)r48[0];
    }
    /* max [max] -> r1027 */
    for (long i21640 = 0; i21640 < 400; ++i21640) {
        r1027[i21640] = max32(r1026[0], r1025[i21640]);
    }
    /* convert [convert_element_type] -> r1028 */
    for (long i21641 = 0; i21641 < 1; ++i21641) {
        r1028[i21641] = (int32_t)r49[0];
    }
    /* min [min] -> r1029 */
    for (long i21642 = 0; i21642 < 400; ++i21642) {
        r1029[i21642] = min32(r1028[0], r1027[i21642]);
    }
    /* sub [sub] -> r1030 */
    for (long i21643 = 0; i21643 < 400; ++i21643) {
        long t21645 = i21643;
        long c216440 = t21645 / 80; t21645 %= 80;
        long c216441 = t21645 / 80; t21645 %= 80;
        long c216442 = t21645 / 16; t21645 %= 16;
        long c216443 = t21645;
        r1030[i21643] = sub32(r1012[c216440 * 16 + c216443 * 1], r1024[c216442 * 16 + c216443 * 1]);
    }
    /* convert [convert_element_type] -> r1031 */
    for (long i21646 = 0; i21646 < 1; ++i21646) {
        r1031[i21646] = (int32_t)r48[0];
    }
    /* max [max] -> r1032 */
    for (long i21647 = 0; i21647 < 400; ++i21647) {
        r1032[i21647] = max32(r1031[0], r1030[i21647]);
    }
    /* convert [convert_element_type] -> r1033 */
    for (long i21648 = 0; i21648 < 1; ++i21648) {
        r1033[i21648] = (int32_t)r49[0];
    }
    /* min [min] -> r1034 */
    for (long i21649 = 0; i21649 < 400; ++i21649) {
        r1034[i21649] = min32(r1033[0], r1032[i21649]);
    }
    /* abs [abs] -> r1035 */
    for (long i21650 = 0; i21650 < 400; ++i21650) {
        r1035[i21650] = abs32(r1029[i21650]);
    }
    /* reduce_max [reduce_max] -> r1036 */
    for (long i21651 = 0; i21651 < 25; ++i21651) {
        r1036[i21651] = (-2147483647 - 1);
    }
    for (long i21652 = 0; i21652 < 400; ++i21652) {
        long t21654 = i21652;
        long c216530 = t21654 / 80; t21654 %= 80;
        long c216531 = t21654 / 80; t21654 %= 80;
        long c216532 = t21654 / 16; t21654 %= 16;
        long c216533 = t21654;
        r1036[c216530 * 5 + c216531 * 5 + c216532 * 1] = max32(r1036[c216530 * 5 + c216531 * 5 + c216532 * 1], r1035[i21652]);
    }
    /* sub [sub] -> r1037 */
    for (long i21655 = 0; i21655 < 25; ++i21655) {
        r1037[i21655] = sub32(r1036[i21655], r61[0]);
    }
    /* loop [scan] -> r1059 */
    memcpy(r1038, r1029, sizeof(int32_t) * 400);
    memcpy(r1039, r61, sizeof(int32_t) * 1);
    memcpy(r1040, r39, sizeof(int32_t) * 1);
    memcpy(r1041, r1037, sizeof(int32_t) * 25);
    memcpy(r1042, r1036, sizeof(int32_t) * 25);
    for (long t21656 = 0; t21656 < 12; ++t21656) {
        /* add [add] -> r1043 */
        for (long i22657 = 0; i22657 < 1; ++i22657) {
            r1043[i22657] = add32(r1040[0], r30[0]);
        }
        /* add [add] -> r1044 */
        for (long i22658 = 0; i22658 < 25; ++i22658) {
            r1044[i22658] = add32(r1041[i22658], r1042[i22658]);
        }
        /* shra [shift_right_arithmetic] -> r1045 */
        for (long i22659 = 0; i22659 < 25; ++i22659) {
            r1045[i22659] = asr32(r1044[i22659], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1046 */
        for (long i22660 = 0; i22660 < 25; ++i22660) {
            long t22662 = i22660;
            long c226610 = t22662 / 5; t22662 %= 5;
            long c226611 = t22662 / 5; t22662 %= 5;
            long c226612 = t22662 / 1; t22662 %= 1;
            long c226613 = t22662;
            r1046[i22660] = r1045[c226610 * 5 + c226612 * 1];
        }
        /* sub [sub] -> r1047 */
        for (long i22663 = 0; i22663 < 400; ++i22663) {
            long t22665 = i22663;
            long c226640 = t22665 / 80; t22665 %= 80;
            long c226641 = t22665 / 80; t22665 %= 80;
            long c226642 = t22665 / 16; t22665 %= 16;
            long c226643 = t22665;
            r1047[i22663] = sub32(r1038[c226640 * 80 + c226642 * 16 + c226643 * 1], r1046[c226640 * 5 + c226642 * 1]);
        }
        /* max [max] -> r1048 */
        for (long i22666 = 0; i22666 < 400; ++i22666) {
            r1048[i22666] = max32(r1047[i22666], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r1049 */
        for (long i22667 = 0; i22667 < 25; ++i22667) {
            r1049[i22667] = 0;
        }
        for (long i22668 = 0; i22668 < 400; ++i22668) {
            long t22670 = i22668;
            long c226690 = t22670 / 80; t22670 %= 80;
            long c226691 = t22670 / 80; t22670 %= 80;
            long c226692 = t22670 / 16; t22670 %= 16;
            long c226693 = t22670;
            r1049[c226690 * 5 + c226691 * 5 + c226692 * 1] = add32(r1049[c226690 * 5 + c226691 * 5 + c226692 * 1], r1048[i22668]);
        }
        /* neg [neg] -> r1050 */
        for (long i22671 = 0; i22671 < 400; ++i22671) {
            r1050[i22671] = neg32(r1038[i22671]);
        }
        /* broadcast [broadcast_in_dim] -> r1051 */
        for (long i22672 = 0; i22672 < 25; ++i22672) {
            long t22674 = i22672;
            long c226730 = t22674 / 5; t22674 %= 5;
            long c226731 = t22674 / 5; t22674 %= 5;
            long c226732 = t22674 / 1; t22674 %= 1;
            long c226733 = t22674;
            r1051[i22672] = r1045[c226730 * 5 + c226732 * 1];
        }
        /* sub [sub] -> r1052 */
        for (long i22675 = 0; i22675 < 400; ++i22675) {
            long t22677 = i22675;
            long c226760 = t22677 / 80; t22677 %= 80;
            long c226761 = t22677 / 80; t22677 %= 80;
            long c226762 = t22677 / 16; t22677 %= 16;
            long c226763 = t22677;
            r1052[i22675] = sub32(r1050[c226760 * 80 + c226762 * 16 + c226763 * 1], r1051[c226760 * 5 + c226762 * 1]);
        }
        /* max [max] -> r1053 */
        for (long i22678 = 0; i22678 < 400; ++i22678) {
            r1053[i22678] = max32(r1052[i22678], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r1054 */
        for (long i22679 = 0; i22679 < 25; ++i22679) {
            r1054[i22679] = 0;
        }
        for (long i22680 = 0; i22680 < 400; ++i22680) {
            long t22682 = i22680;
            long c226810 = t22682 / 80; t22682 %= 80;
            long c226811 = t22682 / 80; t22682 %= 80;
            long c226812 = t22682 / 16; t22682 %= 16;
            long c226813 = t22682;
            r1054[c226810 * 5 + c226811 * 5 + c226812 * 1] = add32(r1054[c226810 * 5 + c226811 * 5 + c226812 * 1], r1053[i22680]);
        }
        /* add [add] -> r1055 */
        for (long i22683 = 0; i22683 < 25; ++i22683) {
            r1055[i22683] = add32(r1049[i22683], r1054[i22683]);
        }
        /* gt [gt] -> r1056 */
        for (long i22684 = 0; i22684 < 25; ++i22684) {
            r1056[i22684] = r1055[i22684] > r1039[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1057 */
        for (long i22685 = 0; i22685 < 25; ++i22685) {
            r1057[i22685] = r1056[i22685] == 0 ? r1041[i22685] : (r1045[i22685]);
        }
        /* select_n [select_n] -> r1058 */
        for (long i22686 = 0; i22686 < 25; ++i22686) {
            r1058[i22686] = r1056[i22686] == 0 ? r1045[i22686] : (r1042[i22686]);
        }
        memcpy(r1040, r1043, sizeof(int32_t) * 1);
        memcpy(r1041, r1057, sizeof(int32_t) * 25);
        memcpy(r1042, r1058, sizeof(int32_t) * 25);
    }
    memcpy(r1059, r1040, sizeof(int32_t) * 1);
    memcpy(r1060, r1041, sizeof(int32_t) * 25);
    memcpy(r1061, r1042, sizeof(int32_t) * 25);
    /* abs [abs] -> r1062 */
    for (long i22687 = 0; i22687 < 400; ++i22687) {
        r1062[i22687] = abs32(r1034[i22687]);
    }
    /* reduce_max [reduce_max] -> r1063 */
    for (long i22688 = 0; i22688 < 25; ++i22688) {
        r1063[i22688] = (-2147483647 - 1);
    }
    for (long i22689 = 0; i22689 < 400; ++i22689) {
        long t22691 = i22689;
        long c226900 = t22691 / 80; t22691 %= 80;
        long c226901 = t22691 / 80; t22691 %= 80;
        long c226902 = t22691 / 16; t22691 %= 16;
        long c226903 = t22691;
        r1063[c226900 * 5 + c226901 * 5 + c226902 * 1] = max32(r1063[c226900 * 5 + c226901 * 5 + c226902 * 1], r1062[i22689]);
    }
    /* sub [sub] -> r1064 */
    for (long i22692 = 0; i22692 < 25; ++i22692) {
        r1064[i22692] = sub32(r1063[i22692], r61[0]);
    }
    /* loop [scan] -> r1086 */
    memcpy(r1065, r1034, sizeof(int32_t) * 400);
    memcpy(r1066, r61, sizeof(int32_t) * 1);
    memcpy(r1067, r39, sizeof(int32_t) * 1);
    memcpy(r1068, r1064, sizeof(int32_t) * 25);
    memcpy(r1069, r1063, sizeof(int32_t) * 25);
    for (long t22693 = 0; t22693 < 12; ++t22693) {
        /* add [add] -> r1070 */
        for (long i23694 = 0; i23694 < 1; ++i23694) {
            r1070[i23694] = add32(r1067[0], r30[0]);
        }
        /* add [add] -> r1071 */
        for (long i23695 = 0; i23695 < 25; ++i23695) {
            r1071[i23695] = add32(r1068[i23695], r1069[i23695]);
        }
        /* shra [shift_right_arithmetic] -> r1072 */
        for (long i23696 = 0; i23696 < 25; ++i23696) {
            r1072[i23696] = asr32(r1071[i23696], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1073 */
        for (long i23697 = 0; i23697 < 25; ++i23697) {
            long t23699 = i23697;
            long c236980 = t23699 / 5; t23699 %= 5;
            long c236981 = t23699 / 5; t23699 %= 5;
            long c236982 = t23699 / 1; t23699 %= 1;
            long c236983 = t23699;
            r1073[i23697] = r1072[c236980 * 5 + c236982 * 1];
        }
        /* sub [sub] -> r1074 */
        for (long i23700 = 0; i23700 < 400; ++i23700) {
            long t23702 = i23700;
            long c237010 = t23702 / 80; t23702 %= 80;
            long c237011 = t23702 / 80; t23702 %= 80;
            long c237012 = t23702 / 16; t23702 %= 16;
            long c237013 = t23702;
            r1074[i23700] = sub32(r1065[c237010 * 80 + c237012 * 16 + c237013 * 1], r1073[c237010 * 5 + c237012 * 1]);
        }
        /* max [max] -> r1075 */
        for (long i23703 = 0; i23703 < 400; ++i23703) {
            r1075[i23703] = max32(r1074[i23703], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r1076 */
        for (long i23704 = 0; i23704 < 25; ++i23704) {
            r1076[i23704] = 0;
        }
        for (long i23705 = 0; i23705 < 400; ++i23705) {
            long t23707 = i23705;
            long c237060 = t23707 / 80; t23707 %= 80;
            long c237061 = t23707 / 80; t23707 %= 80;
            long c237062 = t23707 / 16; t23707 %= 16;
            long c237063 = t23707;
            r1076[c237060 * 5 + c237061 * 5 + c237062 * 1] = add32(r1076[c237060 * 5 + c237061 * 5 + c237062 * 1], r1075[i23705]);
        }
        /* neg [neg] -> r1077 */
        for (long i23708 = 0; i23708 < 400; ++i23708) {
            r1077[i23708] = neg32(r1065[i23708]);
        }
        /* broadcast [broadcast_in_dim] -> r1078 */
        for (long i23709 = 0; i23709 < 25; ++i23709) {
            long t23711 = i23709;
            long c237100 = t23711 / 5; t23711 %= 5;
            long c237101 = t23711 / 5; t23711 %= 5;
            long c237102 = t23711 / 1; t23711 %= 1;
            long c237103 = t23711;
            r1078[i23709] = r1072[c237100 * 5 + c237102 * 1];
        }
        /* sub [sub] -> r1079 */
        for (long i23712 = 0; i23712 < 400; ++i23712) {
            long t23714 = i23712;
            long c237130 = t23714 / 80; t23714 %= 80;
            long c237131 = t23714 / 80; t23714 %= 80;
            long c237132 = t23714 / 16; t23714 %= 16;
            long c237133 = t23714;
            r1079[i23712] = sub32(r1077[c237130 * 80 + c237132 * 16 + c237133 * 1], r1078[c237130 * 5 + c237132 * 1]);
        }
        /* max [max] -> r1080 */
        for (long i23715 = 0; i23715 < 400; ++i23715) {
            r1080[i23715] = max32(r1079[i23715], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r1081 */
        for (long i23716 = 0; i23716 < 25; ++i23716) {
            r1081[i23716] = 0;
        }
        for (long i23717 = 0; i23717 < 400; ++i23717) {
            long t23719 = i23717;
            long c237180 = t23719 / 80; t23719 %= 80;
            long c237181 = t23719 / 80; t23719 %= 80;
            long c237182 = t23719 / 16; t23719 %= 16;
            long c237183 = t23719;
            r1081[c237180 * 5 + c237181 * 5 + c237182 * 1] = add32(r1081[c237180 * 5 + c237181 * 5 + c237182 * 1], r1080[i23717]);
        }
        /* add [add] -> r1082 */
        for (long i23720 = 0; i23720 < 25; ++i23720) {
            r1082[i23720] = add32(r1076[i23720], r1081[i23720]);
        }
        /* gt [gt] -> r1083 */
        for (long i23721 = 0; i23721 < 25; ++i23721) {
            r1083[i23721] = r1082[i23721] > r1066[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1084 */
        for (long i23722 = 0; i23722 < 25; ++i23722) {
            r1084[i23722] = r1083[i23722] == 0 ? r1068[i23722] : (r1072[i23722]);
        }
        /* select_n [select_n] -> r1085 */
        for (long i23723 = 0; i23723 < 25; ++i23723) {
            r1085[i23723] = r1083[i23723] == 0 ? r1072[i23723] : (r1069[i23723]);
        }
        memcpy(r1067, r1070, sizeof(int32_t) * 1);
        memcpy(r1068, r1084, sizeof(int32_t) * 25);
        memcpy(r1069, r1085, sizeof(int32_t) * 25);
    }
    memcpy(r1086, r1067, sizeof(int32_t) * 1);
    memcpy(r1087, r1068, sizeof(int32_t) * 25);
    memcpy(r1088, r1069, sizeof(int32_t) * 25);
    /* sub [sub] -> r1089 */
    for (long i23724 = 0; i23724 < 25; ++i23724) {
        r1089[i23724] = sub32(r1061[i23724], r1088[i23724]);
    }
    /* transpose [transpose] -> r1090 */
    for (long i23725 = 0; i23725 < 25; ++i23725) {
        long t23727 = i23725;
        long c237260 = t23727 / 25; t23727 %= 25;
        long c237261 = t23727 / 5; t23727 %= 5;
        long c237262 = t23727;
        r1090[i23725] = r1089[c237260 * 5 + c237261 * 5 + c237262 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r1091 */
    for (long i23728 = 0; i23728 < 1; ++i23728) {
        long t23730 = i23728;
        long c237290 = t23730 / 1; t23730 %= 1;
        long c237291 = t23730;
        r1091[i23728] = r1008[0];
    }
    /* max [max] -> r1092 */
    for (long i23731 = 0; i23731 < 25; ++i23731) {
        r1092[i23731] = max32(r1090[i23731], r39[0]);
    }
    /* iota [iota] -> r1093 */
    for (long i23732 = 0; i23732 < 25; ++i23732) {
        long t23734 = i23732;
        long c237330 = t23734 / 25; t23734 %= 25;
        long c237331 = t23734 / 5; t23734 %= 5;
        long c237332 = t23734;
        r1093[i23732] = (int32_t)c237332;
    }
    /* broadcast [broadcast_in_dim] -> r1094 */
    for (long i23735 = 0; i23735 < 1; ++i23735) {
        long t23737 = i23735;
        long c237360 = t23737 / 1; t23737 %= 1;
        long c237361 = t23737 / 1; t23737 %= 1;
        long c237362 = t23737;
        r1094[i23735] = r1091[0];
    }
    /* lt [lt] -> r1095 */
    for (long i23738 = 0; i23738 < 25; ++i23738) {
        long t23740 = i23738;
        long c237390 = t23740 / 25; t23740 %= 25;
        long c237391 = t23740 / 5; t23740 %= 5;
        long c237392 = t23740;
        r1095[i23738] = r1093[c237391 * 5 + c237392 * 1] < r1094[0] ? 1 : 0;
    }
    /* convert [convert_element_type] -> r1096 */
    for (long i23741 = 0; i23741 < 1; ++i23741) {
        r1096[i23741] = (int32_t)r39[0];
    }
    /* broadcast [broadcast_in_dim] -> r1097 */
    for (long i23742 = 0; i23742 < 25; ++i23742) {
        long t23744 = i23742;
        long c237430 = t23744 / 25; t23744 %= 25;
        long c237431 = t23744 / 5; t23744 %= 5;
        long c237432 = t23744;
        r1097[i23742] = r1096[0];
    }
    /* select_n [select_n] -> r1098 */
    for (long i23745 = 0; i23745 < 25; ++i23745) {
        r1098[i23745] = r1095[i23745] == 0 ? r1097[i23745] : (r1092[i23745]);
    }
    /* reduce_sum [reduce_sum] -> r1099 */
    for (long i23746 = 0; i23746 < 5; ++i23746) {
        r1099[i23746] = 0;
    }
    for (long i23747 = 0; i23747 < 25; ++i23747) {
        long t23749 = i23747;
        long c237480 = t23749 / 25; t23749 %= 25;
        long c237481 = t23749 / 5; t23749 %= 5;
        long c237482 = t23749;
        r1099[c237480 * 5 + c237481 * 1] = add32(r1099[c237480 * 5 + c237481 * 1], r1098[i23747]);
    }
    /* shl [shift_left] -> r1101 */
    for (long i23750 = 0; i23750 < 5; ++i23750) {
        r1101[i23750] = shl32(r1099[i23750], 5);
    }
    /* lt [lt] -> r1102 */
    for (long i23751 = 0; i23751 < 1; ++i23751) {
        r1102[i23751] = r1008[i23751] < r39[0] ? 1 : 0;
    }
    /* add [add] -> r1103 */
    for (long i23752 = 0; i23752 < 1; ++i23752) {
        r1103[i23752] = add32(r1008[i23752], r1019[0]);
    }
    /* select_n [select_n] -> r1104 */
    for (long i23753 = 0; i23753 < 1; ++i23753) {
        r1104[i23753] = r1102[i23753] == 0 ? r1008[i23753] : (r1103[i23753]);
    }
    /* broadcast [broadcast_in_dim] -> r1105 */
    for (long i23754 = 0; i23754 < 1; ++i23754) {
        long t23756 = i23754;
        long c237550 = t23756 / 1; t23756 %= 1;
        long c237551 = t23756;
        r1105[i23754] = r1104[0];
    }
    /* gather [gather] -> r1106 */
    for (long i23757 = 0; i23757 < 15; ++i23757) {
        long t23759 = i23757;
        long c237580 = t23759 / 15; t23759 %= 15;
        long c237581 = t23759;
        long row23760 = c237580 * 1;
        long s23761 = clamp_start((long)r1105[row23760 + 0], 20, 15);
        r1106[i23757] = r1009[c237580 * 20 + (s23761 + c237581) * 1];
    }
    /* add [add] -> r1107 */
    for (long i23762 = 0; i23762 < 1; ++i23762) {
        r1107[i23762] = add32(r11[i23762], r1008[i23762]);
    }
    /* concat [concatenate] -> r1108 */
    for (long i23763 = 0; i23763 < 5; ++i23763) {
        long t23765 = i23763;
        long c237640 = t23765 / 5; t23765 %= 5;
        long c237641 = t23765;
        r1108[c237640 * 30 + (c237641 + 0) * 1] = r125[i23763];
    }
    for (long i23766 = 0; i23766 < 5; ++i23766) {
        long t23768 = i23766;
        long c237670 = t23768 / 5; t23768 %= 5;
        long c237671 = t23768;
        r1108[c237670 * 30 + (c237671 + 5) * 1] = r321[i23766];
    }
    for (long i23769 = 0; i23769 < 5; ++i23769) {
        long t23771 = i23769;
        long c237700 = t23771 / 5; t23771 %= 5;
        long c237701 = t23771;
        r1108[c237700 * 30 + (c237701 + 10) * 1] = r516[i23769];
    }
    for (long i23772 = 0; i23772 < 5; ++i23772) {
        long t23774 = i23772;
        long c237730 = t23774 / 5; t23774 %= 5;
        long c237731 = t23774;
        r1108[c237730 * 30 + (c237731 + 15) * 1] = r711[i23772];
    }
    for (long i23775 = 0; i23775 < 5; ++i23775) {
        long t23777 = i23775;
        long c237760 = t23777 / 5; t23777 %= 5;
        long c237761 = t23777;
        r1108[c237760 * 30 + (c237761 + 20) * 1] = r906[i23775];
    }
    for (long i23778 = 0; i23778 < 5; ++i23778) {
        long t23780 = i23778;
        long c237790 = t23780 / 5; t23780 %= 5;
        long c237791 = t23780;
        r1108[c237790 * 30 + (c237791 + 25) * 1] = r1101[i23778];
    }
    /* add [add] -> r1109 */
    for (long i23781 = 0; i23781 < 30; ++i23781) {
        r1109[i23781] = add32(r12[i23781], r1108[i23781]);
    }
    /* add [add] -> r1110 */
    for (long i23782 = 0; i23782 < 1; ++i23782) {
        r1110[i23782] = add32(r14[i23782], r17[i23782]);
    }
    /* broadcast [broadcast_in_dim] -> r1111 */
    for (long i23783 = 0; i23783 < 30; ++i23783) {
        long t23785 = i23783;
        long c237840 = t23785 / 30; t23785 %= 30;
        long c237841 = t23785;
        r1111[i23783] = r20[c237841 * 1];
    }
    /* sub [sub] -> r1112 */
    for (long i23786 = 0; i23786 < 30; ++i23786) {
        r1112[i23786] = sub32(r1109[i23786], r1111[i23786]);
    }
    /* ge [ge] -> r1113 */
    for (long i23787 = 0; i23787 < 30; ++i23787) {
        r1113[i23787] = r21[i23787] >= r39[0] ? 1 : 0;
    }
    /* max [max] -> r1114 */
    for (long i23788 = 0; i23788 < 30; ++i23788) {
        r1114[i23788] = max32(r21[i23788], r39[0]);
    }
    /* broadcast [broadcast_in_dim] -> r1115 */
    for (long i23789 = 0; i23789 < 30; ++i23789) {
        long t23791 = i23789;
        long c237900 = t23791 / 30; t23791 %= 30;
        long c237901 = t23791;
        r1115[i23789] = r1114[c237901 * 1];
    }
    /* shl [shift_left] -> r1116 */
    for (long i23792 = 0; i23792 < 30; ++i23792) {
        r1116[i23792] = shl32(r1112[i23792], r1115[i23792]);
    }
    /* neg [neg] -> r1117 */
    for (long i23793 = 0; i23793 < 30; ++i23793) {
        r1117[i23793] = neg32(r21[i23793]);
    }
    /* max [max] -> r1118 */
    for (long i23794 = 0; i23794 < 30; ++i23794) {
        r1118[i23794] = max32(r1117[i23794], r39[0]);
    }
    /* broadcast [broadcast_in_dim] -> r1119 */
    for (long i23795 = 0; i23795 < 30; ++i23795) {
        long t23797 = i23795;
        long c237960 = t23797 / 30; t23797 %= 30;
        long c237961 = t23797;
        r1119[i23795] = r1118[c237961 * 1];
    }
    /* shra [shift_right_arithmetic] -> r1120 */
    for (long i23798 = 0; i23798 < 30; ++i23798) {
        r1120[i23798] = asr32(r1112[i23798], r1119[i23798]);
    }
    /* broadcast [broadcast_in_dim] -> r1121 */
    for (long i23799 = 0; i23799 < 30; ++i23799) {
        long t23801 = i23799;
        long c238000 = t23801 / 30; t23801 %= 30;
        long c238001 = t23801;
        r1121[i23799] = r1113[c238001 * 1];
    }
    /* select_n [select_n] -> r1122 */
    for (long i23802 = 0; i23802 < 30; ++i23802) {
        r1122[i23802] = r1121[i23802] == 0 ? r1120[i23802] : (r1116[i23802]);
    }
    /* ge [ge] -> r1123 */
    for (long i23803 = 0; i23803 < 30; ++i23803) {
        r1123[i23803] = r22[i23803] >= r39[0] ? 1 : 0;
    }
    /* max [max] -> r1124 */
    for (long i23804 = 0; i23804 < 30; ++i23804) {
        r1124[i23804] = max32(r22[i23804], r39[0]);
    }
    /* broadcast [broadcast_in_dim] -> r1125 */
    for (long i23805 = 0; i23805 < 30; ++i23805) {
        long t23807 = i23805;
        long c238060 = t23807 / 30; t23807 %= 30;
        long c238061 = t23807;
        r1125[i23805] = r1124[c238061 * 1];
    }
    /* shl [shift_left] -> r1126 */
    for (long i23808 = 0; i23808 < 30; ++i23808) {
        r1126[i23808] = shl32(r1112[i23808], r1125[i23808]);
    }
    /* neg [neg] -> r1127 */
    for (long i23809 = 0; i23809 < 30; ++i23809) {
        r1127[i23809] = neg32(r22[i23809]);
    }
    /* max [max] -> r1128 */
    for (long i23810 = 0; i23810 < 30; ++i23810) {
        r1128[i23810] = max32(r1127[i23810], r39[0]);
    }
    /* broadcast [broadcast_in_dim] -> r1129 */
    for (long i23811 = 0; i23811 < 30; ++i23811) {
        long t23813 = i23811;
        long c238120 = t23813 / 30; t23813 %= 30;
        long c238121 = t23813;
        r1129[i23811] = r1128[c238121 * 1];
    }
    /* shra [shift_right_arithmetic] -> r1130 */
    for (long i23814 = 0; i23814 < 30; ++i23814) {
        r1130[i23814] = asr32(r1112[i23814], r1129[i23814]);
    }
    /* broadcast [broadcast_in_dim] -> r1131 */
    for (long i23815 = 0; i23815 < 30; ++i23815) {
        long t23817 = i23815;
        long c238160 = t23817 / 30; t23817 %= 30;
        long c238161 = t23817;
        r1131[i23815] = r1123[c238161 * 1];
    }
    /* select_n [select_n] -> r1132 */
    for (long i23818 = 0; i23818 < 30; ++i23818) {
        r1132[i23818] = r1131[i23818] == 0 ? r1130[i23818] : (r1126[i23818]);
    }
    /* gt [gt] -> r1133 */
    for (long i23819 = 0; i23819 < 30; ++i23819) {
        r1133[i23819] = r20[i23819] > r39[0] ? 1 : 0;
    }
    /* add [add] -> r1134 */
    for (long i23820 = 0; i23820 < 30; ++i23820) {
        r1134[i23820] = add32(r1122[i23820], r1132[i23820]);
    }
    /* lt [lt] -> r1135 */
    for (long i23821 = 0; i23821 < 30; ++i23821) {
        r1135[i23821] = r20[i23821] < r39[0] ? 1 : 0;
    }
    /* sub [sub] -> r1136 */
    for (long i23822 = 0; i23822 < 30; ++i23822) {
        r1136[i23822] = sub32(r1122[i23822], r1132[i23822]);
    }
    /* broadcast [broadcast_in_dim] -> r1137 */
    for (long i23823 = 0; i23823 < 30; ++i23823) {
        long t23825 = i23823;
        long c238240 = t23825 / 30; t23825 %= 30;
        long c238241 = t23825;
        r1137[i23823] = r1135[c238241 * 1];
    }
    /* select_n [select_n] -> r1138 */
    for (long i23826 = 0; i23826 < 30; ++i23826) {
        r1138[i23826] = r1137[i23826] == 0 ? r1122[i23826] : (r1136[i23826]);
    }
    /* broadcast [broadcast_in_dim] -> r1139 */
    for (long i23827 = 0; i23827 < 30; ++i23827) {
        long t23829 = i23827;
        long c238280 = t23829 / 30; t23829 %= 30;
        long c238281 = t23829;
        r1139[i23827] = r1133[c238281 * 1];
    }
    /* select_n [select_n] -> r1140 */
    for (long i23830 = 0; i23830 < 30; ++i23830) {
        r1140[i23830] = r1139[i23830] == 0 ? r1138[i23830] : (r1134[i23830]);
    }
    /* convert [convert_element_type] -> r1141 */
    for (long i23831 = 0; i23831 < 1; ++i23831) {
        r1141[i23831] = (int32_t)r220[0];
    }
    /* max [max] -> r1142 */
    for (long i23832 = 0; i23832 < 30; ++i23832) {
        r1142[i23832] = max32(r1141[0], r1140[i23832]);
    }
    /* convert [convert_element_type] -> r1143 */
    for (long i23833 = 0; i23833 < 1; ++i23833) {
        r1143[i23833] = (int32_t)r221[0];
    }
    /* min [min] -> r1144 */
    for (long i23834 = 0; i23834 < 30; ++i23834) {
        r1144[i23834] = min32(r1143[0], r1142[i23834]);
    }
    /* shl [shift_left] -> r1145 */
    for (long i23835 = 0; i23835 < 30; ++i23835) {
        r1145[i23835] = shl32(r1144[i23835], 1);
    }
    /* broadcast [broadcast_in_dim] -> r1146 */
    for (long i23836 = 0; i23836 < 30; ++i23836) {
        long t23838 = i23836;
        long c238370 = t23838 / 30; t23838 %= 30;
        long c238371 = t23838 / 1; t23838 %= 1;
        long c238372 = t23838;
        r1146[i23836] = r1145[c238371 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r1147 */
    for (long i23839 = 0; i23839 < 30; ++i23839) {
        long t23841 = i23839;
        long c238400 = t23841 / 30; t23841 %= 30;
        long c238401 = t23841 / 1; t23841 %= 1;
        long c238402 = t23841;
        r1147[i23839] = r1145[c238401 * 1];
    }
    /* neg [neg] -> r1148 */
    for (long i23842 = 0; i23842 < 30; ++i23842) {
        r1148[i23842] = neg32(r1147[i23842]);
    }
    /* broadcast [broadcast_in_dim] -> r1149 */
    for (long i23843 = 0; i23843 < 300; ++i23843) {
        long t23845 = i23843;
        long c238440 = t23845 / 300; t23845 %= 300;
        long c238441 = t23845 / 10; t23845 %= 10;
        long c238442 = t23845;
        r1149[i23843] = r23[c238441 * 10 + c238442 * 1];
    }
    /* add [add] -> r1150 */
    for (long i23846 = 0; i23846 < 300; ++i23846) {
        long t23848 = i23846;
        long c238470 = t23848 / 300; t23848 %= 300;
        long c238471 = t23848 / 10; t23848 %= 10;
        long c238472 = t23848;
        r1150[i23846] = add32(r1149[c238471 * 10 + c238472 * 1], r1146[c238471 * 1]);
    }
    /* convert [convert_element_type] -> r1151 */
    for (long i23849 = 0; i23849 < 1; ++i23849) {
        r1151[i23849] = (int32_t)r48[0];
    }
    /* max [max] -> r1152 */
    for (long i23850 = 0; i23850 < 300; ++i23850) {
        r1152[i23850] = max32(r1151[0], r1150[i23850]);
    }
    /* convert [convert_element_type] -> r1153 */
    for (long i23851 = 0; i23851 < 1; ++i23851) {
        r1153[i23851] = (int32_t)r49[0];
    }
    /* min [min] -> r1154 */
    for (long i23852 = 0; i23852 < 300; ++i23852) {
        r1154[i23852] = min32(r1153[0], r1152[i23852]);
    }
    /* broadcast [broadcast_in_dim] -> r1155 */
    for (long i23853 = 0; i23853 < 300; ++i23853) {
        long t23855 = i23853;
        long c238540 = t23855 / 300; t23855 %= 300;
        long c238541 = t23855 / 10; t23855 %= 10;
        long c238542 = t23855;
        r1155[i23853] = r24[c238541 * 10 + c238542 * 1];
    }
    /* add [add] -> r1156 */
    for (long i23856 = 0; i23856 < 300; ++i23856) {
        long t23858 = i23856;
        long c238570 = t23858 / 300; t23858 %= 300;
        long c238571 = t23858 / 10; t23858 %= 10;
        long c238572 = t23858;
        r1156[i23856] = add32(r1155[c238571 * 10 + c238572 * 1], r1148[c238571 * 1]);
    }
    /* convert [convert_element_type] -> r1157 */
    for (long i23859 = 0; i23859 < 1; ++i23859) {
        r1157[i23859] = (int32_t)r48[0];
    }
    /* max [max] -> r1158 */
    for (long i23860 = 0; i23860 < 300; ++i23860) {
        r1158[i23860] = max32(r1157[0], r1156[i23860]);
    }
    /* convert [convert_element_type] -> r1159 */
    for (long i23861 = 0; i23861 < 1; ++i23861) {
        r1159[i23861] = (int32_t)r49[0];
    }
    /* min [min] -> r1160 */
    for (long i23862 = 0; i23862 < 300; ++i23862) {
        r1160[i23862] = min32(r1159[0], r1158[i23862]);
    }
    /* concat [concatenate] -> r1161 */
    for (long i23863 = 0; i23863 < 300; ++i23863) {
        long t23865 = i23863;
        long c238640 = t23865 / 300; t23865 %= 300;
        long c238641 = t23865 / 10; t23865 %= 10;
        long c238642 = t23865;
        r1161[c238640 * 600 + (c238641 + 0) * 10 + c238642 * 1] = r1154[i23863];
    }
    for (long i23866 = 0; i23866 < 300; ++i23866) {
        long t23868 = i23866;
        long c238670 = t23868 / 300; t23868 %= 300;
        long c238671 = t23868 / 10; t23868 %= 10;
        long c238672 = t23868;
        r1161[c238670 * 600 + (c238671 + 30) * 10 + c238672 * 1] = r1160[i23866];
    }
    /* broadcast [broadcast_in_dim] -> r1162 */
    for (long i23869 = 0; i23869 < 10; ++i23869) {
        long t23871 = i23869;
        long c238700 = t23871 / 10; t23871 %= 10;
        long c238701 = t23871 / 10; t23871 %= 10;
        long c238702 = t23871;
        r1162[i23869] = r25[c238702 * 1];
    }
    /* concat [concatenate] -> r1163 */
    for (long i23872 = 0; i23872 < 600; ++i23872) {
        long t23874 = i23872;
        long c238730 = t23874 / 600; t23874 %= 600;
        long c238731 = t23874 / 10; t23874 %= 10;
        long c238732 = t23874;
        r1163[c238730 * 610 + (c238731 + 0) * 10 + c238732 * 1] = r1161[i23872];
    }
    for (long i23875 = 0; i23875 < 10; ++i23875) {
        long t23877 = i23875;
        long c238760 = t23877 / 10; t23877 %= 10;
        long c238761 = t23877 / 10; t23877 %= 10;
        long c238762 = t23877;
        r1163[c238760 * 610 + (c238761 + 60) * 10 + c238762 * 1] = r1162[i23875];
    }
    /* transpose [transpose] -> r1164 */
    for (long i23878 = 0; i23878 < 610; ++i23878) {
        long t23880 = i23878;
        long c238790 = t23880 / 610; t23880 %= 610;
        long c238791 = t23880 / 61; t23880 %= 61;
        long c238792 = t23880;
        r1164[i23878] = r1163[c238790 * 610 + c238791 * 1 + c238792 * 10];
    }
    /* reduce_max [reduce_max] -> r1165 */
    for (long i23881 = 0; i23881 < 10; ++i23881) {
        r1165[i23881] = (-2147483647 - 1);
    }
    for (long i23882 = 0; i23882 < 610; ++i23882) {
        long t23884 = i23882;
        long c238830 = t23884 / 610; t23884 %= 610;
        long c238831 = t23884 / 61; t23884 %= 61;
        long c238832 = t23884;
        r1165[c238830 * 10 + c238831 * 1] = max32(r1165[c238830 * 10 + c238831 * 1], r1164[i23882]);
    }
    /* sub [sub] -> r1167 */
    for (long i23885 = 0; i23885 < 10; ++i23885) {
        r1167[i23885] = sub32(r1165[i23885], r1166[0]);
    }
    /* loop [scan] -> r1183 */
    memcpy(r1168, r1164, sizeof(int32_t) * 610);
    memcpy(r1169, r1166, sizeof(int32_t) * 1);
    memcpy(r1170, r39, sizeof(int32_t) * 1);
    memcpy(r1171, r1167, sizeof(int32_t) * 10);
    memcpy(r1172, r1165, sizeof(int32_t) * 10);
    for (long t23886 = 0; t23886 < 11; ++t23886) {
        /* add [add] -> r1173 */
        for (long i24887 = 0; i24887 < 1; ++i24887) {
            r1173[i24887] = add32(r1170[0], r30[0]);
        }
        /* add [add] -> r1174 */
        for (long i24888 = 0; i24888 < 10; ++i24888) {
            r1174[i24888] = add32(r1171[i24888], r1172[i24888]);
        }
        /* shra [shift_right_arithmetic] -> r1175 */
        for (long i24889 = 0; i24889 < 10; ++i24889) {
            r1175[i24889] = asr32(r1174[i24889], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1176 */
        for (long i24890 = 0; i24890 < 10; ++i24890) {
            long t24892 = i24890;
            long c248910 = t24892 / 10; t24892 %= 10;
            long c248911 = t24892 / 1; t24892 %= 1;
            long c248912 = t24892;
            r1176[i24890] = r1175[c248911 * 1];
        }
        /* sub [sub] -> r1177 */
        for (long i24893 = 0; i24893 < 610; ++i24893) {
            long t24895 = i24893;
            long c248940 = t24895 / 610; t24895 %= 610;
            long c248941 = t24895 / 61; t24895 %= 61;
            long c248942 = t24895;
            r1177[i24893] = sub32(r1168[c248941 * 61 + c248942 * 1], r1176[c248941 * 1]);
        }
        /* max [max] -> r1178 */
        for (long i24896 = 0; i24896 < 610; ++i24896) {
            r1178[i24896] = max32(r1177[i24896], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r1179 */
        for (long i24897 = 0; i24897 < 10; ++i24897) {
            r1179[i24897] = 0;
        }
        for (long i24898 = 0; i24898 < 610; ++i24898) {
            long t24900 = i24898;
            long c248990 = t24900 / 610; t24900 %= 610;
            long c248991 = t24900 / 61; t24900 %= 61;
            long c248992 = t24900;
            r1179[c248990 * 10 + c248991 * 1] = add32(r1179[c248990 * 10 + c248991 * 1], r1178[i24898]);
        }
        /* gt [gt] -> r1180 */
        for (long i24901 = 0; i24901 < 10; ++i24901) {
            r1180[i24901] = r1179[i24901] > r1169[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1181 */
        for (long i24902 = 0; i24902 < 10; ++i24902) {
            r1181[i24902] = r1180[i24902] == 0 ? r1171[i24902] : (r1175[i24902]);
        }
        /* select_n [select_n] -> r1182 */
        for (long i24903 = 0; i24903 < 10; ++i24903) {
            r1182[i24903] = r1180[i24903] == 0 ? r1175[i24903] : (r1172[i24903]);
        }
        memcpy(r1170, r1173, sizeof(int32_t) * 1);
        memcpy(r1171, r1181, sizeof(int32_t) * 10);
        memcpy(r1172, r1182, sizeof(int32_t) * 10);
    }
    memcpy(r1183, r1170, sizeof(int32_t) * 1);
    memcpy(r1184, r1171, sizeof(int32_t) * 10);
    memcpy(r1185, r1172, sizeof(int32_t) * 10);
    /* broadcast [broadcast_in_dim] -> r1186 */
    for (long i24904 = 0; i24904 < 300; ++i24904) {
        long t24906 = i24904;
        long c249050 = t24906 / 300; t24906 %= 300;
        long c249051 = t24906 / 10; t24906 %= 10;
        long c249052 = t24906;
        r1186[i24904] = r24[c249051 * 10 + c249052 * 1];
    }
    /* add [add] -> r1187 */
    for (long i24907 = 0; i24907 < 300; ++i24907) {
        long t24909 = i24907;
        long c249080 = t24909 / 300; t24909 %= 300;
        long c249081 = t24909 / 10; t24909 %= 10;
        long c249082 = t24909;
        r1187[i24907] = add32(r1186[c249081 * 10 + c249082 * 1], r1146[c249081 * 1]);
    }
    /* convert [convert_element_type] -> r1188 */
    for (long i24910 = 0; i24910 < 1; ++i24910) {
        r1188[i24910] = (int32_t)r48[0];
    }
    /* max [max] -> r1189 */
    for (long i24911 = 0; i24911 < 300; ++i24911) {
        r1189[i24911] = max32(r1188[0], r1187[i24911]);
    }
    /* convert [convert_element_type] -> r1190 */
    for (long i24912 = 0; i24912 < 1; ++i24912) {
        r1190[i24912] = (int32_t)r49[0];
    }
    /* min [min] -> r1191 */
    for (long i24913 = 0; i24913 < 300; ++i24913) {
        r1191[i24913] = min32(r1190[0], r1189[i24913]);
    }
    /* broadcast [broadcast_in_dim] -> r1192 */
    for (long i24914 = 0; i24914 < 300; ++i24914) {
        long t24916 = i24914;
        long c249150 = t24916 / 300; t24916 %= 300;
        long c249151 = t24916 / 10; t24916 %= 10;
        long c249152 = t24916;
        r1192[i24914] = r23[c249151 * 10 + c249152 * 1];
    }
    /* add [add] -> r1193 */
    for (long i24917 = 0; i24917 < 300; ++i24917) {
        long t24919 = i24917;
        long c249180 = t24919 / 300; t24919 %= 300;
        long c249181 = t24919 / 10; t24919 %= 10;
        long c249182 = t24919;
        r1193[i24917] = add32(r1192[c249181 * 10 + c249182 * 1], r1148[c249181 * 1]);
    }
    /* convert [convert_element_type] -> r1194 */
    for (long i24920 = 0; i24920 < 1; ++i24920) {
        r1194[i24920] = (int32_t)r48[0];
    }
    /* max [max] -> r1195 */
    for (long i24921 = 0; i24921 < 300; ++i24921) {
        r1195[i24921] = max32(r1194[0], r1193[i24921]);
    }
    /* convert [convert_element_type] -> r1196 */
    for (long i24922 = 0; i24922 < 1; ++i24922) {
        r1196[i24922] = (int32_t)r49[0];
    }
    /* min [min] -> r1197 */
    for (long i24923 = 0; i24923 < 300; ++i24923) {
        r1197[i24923] = min32(r1196[0], r1195[i24923]);
    }
    /* concat [concatenate] -> r1198 */
    for (long i24924 = 0; i24924 < 300; ++i24924) {
        long t24926 = i24924;
        long c249250 = t24926 / 300; t24926 %= 300;
        long c249251 = t24926 / 10; t24926 %= 10;
        long c249252 = t24926;
        r1198[c249250 * 600 + (c249251 + 0) * 10 + c249252 * 1] = r1191[i24924];
    }
    for (long i24927 = 0; i24927 < 300; ++i24927) {
        long t24929 = i24927;
        long c249280 = t24929 / 300; t24929 %= 300;
        long c249281 = t24929 / 10; t24929 %= 10;
        long c249282 = t24929;
        r1198[c249280 * 600 + (c249281 + 30) * 10 + c249282 * 1] = r1197[i24927];
    }
    /* broadcast [broadcast_in_dim] -> r1199 */
    for (long i24930 = 0; i24930 < 10; ++i24930) {
        long t24932 = i24930;
        long c249310 = t24932 / 10; t24932 %= 10;
        long c249311 = t24932 / 10; t24932 %= 10;
        long c249312 = t24932;
        r1199[i24930] = r25[c249312 * 1];
    }
    /* concat [concatenate] -> r1200 */
    for (long i24933 = 0; i24933 < 600; ++i24933) {
        long t24935 = i24933;
        long c249340 = t24935 / 600; t24935 %= 600;
        long c249341 = t24935 / 10; t24935 %= 10;
        long c249342 = t24935;
        r1200[c249340 * 610 + (c249341 + 0) * 10 + c249342 * 1] = r1198[i24933];
    }
    for (long i24936 = 0; i24936 < 10; ++i24936) {
        long t24938 = i24936;
        long c249370 = t24938 / 10; t24938 %= 10;
        long c249371 = t24938 / 10; t24938 %= 10;
        long c249372 = t24938;
        r1200[c249370 * 610 + (c249371 + 60) * 10 + c249372 * 1] = r1199[i24936];
    }
    /* transpose [transpose] -> r1201 */
    for (long i24939 = 0; i24939 < 610; ++i24939) {
        long t24941 = i24939;
        long c249400 = t24941 / 610; t24941 %= 610;
        long c249401 = t24941 / 61; t24941 %= 61;
        long c249402 = t24941;
        r1201[i24939] = r1200[c249400 * 610 + c249401 * 1 + c249402 * 10];
    }
    /* reduce_max [reduce_max] -> r1202 */
    for (long i24942 = 0; i24942 < 10; ++i24942) {
        r1202[i24942] = (-2147483647 - 1);
    }
    for (long i24943 = 0; i24943 < 610; ++i24943) {
        long t24945 = i24943;
        long c249440 = t24945 / 610; t24945 %= 610;
        long c249441 = t24945 / 61; t24945 %= 61;
        long c249442 = t24945;
        r1202[c249440 * 10 + c249441 * 1] = max32(r1202[c249440 * 10 + c249441 * 1], r1201[i24943]);
    }
    /* sub [sub] -> r1203 */
    for (long i24946 = 0; i24946 < 10; ++i24946) {
        r1203[i24946] = sub32(r1202[i24946], r1166[0]);
    }
    /* loop [scan] -> r1219 */
    memcpy(r1204, r1201, sizeof(int32_t) * 610);
    memcpy(r1205, r1166, sizeof(int32_t) * 1);
    memcpy(r1206, r39, sizeof(int32_t) * 1);
    memcpy(r1207, r1203, sizeof(int32_t) * 10);
    memcpy(r1208, r1202, sizeof(int32_t) * 10);
    for (long t24947 = 0; t24947 < 11; ++t24947) {
        /* add [add] -> r1209 */
        for (long i25948 = 0; i25948 < 1; ++i25948) {
            r1209[i25948] = add32(r1206[0], r30[0]);
        }
        /* add [add] -> r1210 */
        for (long i25949 = 0; i25949 < 10; ++i25949) {
            r1210[i25949] = add32(r1207[i25949], r1208[i25949]);
        }
        /* shra [shift_right_arithmetic] -> r1211 */
        for (long i25950 = 0; i25950 < 10; ++i25950) {
            r1211[i25950] = asr32(r1210[i25950], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1212 */
        for (long i25951 = 0; i25951 < 10; ++i25951) {
            long t25953 = i25951;
            long c259520 = t25953 / 10; t25953 %= 10;
            long c259521 = t25953 / 1; t25953 %= 1;
            long c259522 = t25953;
            r1212[i25951] = r1211[c259521 * 1];
        }
        /* sub [sub] -> r1213 */
        for (long i25954 = 0; i25954 < 610; ++i25954) {
            long t25956 = i25954;
            long c259550 = t25956 / 610; t25956 %= 610;
            long c259551 = t25956 / 61; t25956 %= 61;
            long c259552 = t25956;
            r1213[i25954] = sub32(r1204[c259551 * 61 + c259552 * 1], r1212[c259551 * 1]);
        }
        /* max [max] -> r1214 */
        for (long i25957 = 0; i25957 < 610; ++i25957) {
            r1214[i25957] = max32(r1213[i25957], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r1215 */
        for (long i25958 = 0; i25958 < 10; ++i25958) {
            r1215[i25958] = 0;
        }
        for (long i25959 = 0; i25959 < 610; ++i25959) {
            long t25961 = i25959;
            long c259600 = t25961 / 610; t25961 %= 610;
            long c259601 = t25961 / 61; t25961 %= 61;
            long c259602 = t25961;
            r1215[c259600 * 10 + c259601 * 1] = add32(r1215[c259600 * 10 + c259601 * 1], r1214[i25959]);
        }
        /* gt [gt] -> r1216 */
        for (long i25962 = 0; i25962 < 10; ++i25962) {
            r1216[i25962] = r1215[i25962] > r1205[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1217 */
        for (long i25963 = 0; i25963 < 10; ++i25963) {
            r1217[i25963] = r1216[i25963] == 0 ? r1207[i25963] : (r1211[i25963]);
        }
        /* select_n [select_n] -> r1218 */
        for (long i25964 = 0; i25964 < 10; ++i25964) {
            r1218[i25964] = r1216[i25964] == 0 ? r1211[i25964] : (r1208[i25964]);
        }
        memcpy(r1206, r1209, sizeof(int32_t) * 1);
        memcpy(r1207, r1217, sizeof(int32_t) * 10);
        memcpy(r1208, r1218, sizeof(int32_t) * 10);
    }
    memcpy(r1219, r1206, sizeof(int32_t) * 1);
    memcpy(r1220, r1207, sizeof(int32_t) * 10);
    memcpy(r1221, r1208, sizeof(int32_t) * 10);
    /* broadcast [broadcast_in_dim] -> r1222 */
    for (long i25965 = 0; i25965 < 10; ++i25965) {
        long t25967 = i25965;
        long c259660 = t25967 / 10; t25967 %= 10;
        long c259661 = t25967 / 1; t25967 %= 1;
        long c259662 = t25967;
        r1222[i25965] = r1185[c259661 * 1];
    }
    /* broadcast [broadcast_in_dim] -> r1223 */
    for (long i25968 = 0; i25968 < 10; ++i25968) {
        long t25970 = i25968;
        long c259690 = t25970 / 10; t25970 %= 10;
        long c259691 = t25970 / 1; t25970 %= 1;
        long c259692 = t25970;
        r1223[i25968] = r1221[c259691 * 1];
    }
    /* concat [concatenate] -> r1224 */
    for (long i25971 = 0; i25971 < 10; ++i25971) {
        long t25973 = i25971;
        long c259720 = t25973 / 10; t25973 %= 10;
        long c259721 = t25973 / 1; t25973 %= 1;
        long c259722 = t25973;
        r1224[c259720 * 20 + c259721 * 2 + (c259722 + 0) * 1] = r1222[i25971];
    }
    for (long i25974 = 0; i25974 < 10; ++i25974) {
        long t25976 = i25974;
        long c259750 = t25976 / 10; t25976 %= 10;
        long c259751 = t25976 / 1; t25976 %= 1;
        long c259752 = t25976;
        r1224[c259750 * 20 + c259751 * 2 + (c259752 + 1) * 1] = r1223[i25974];
    }
    /* reduce_max [reduce_max] -> r1225 */
    for (long i25977 = 0; i25977 < 10; ++i25977) {
        r1225[i25977] = (-2147483647 - 1);
    }
    for (long i25978 = 0; i25978 < 20; ++i25978) {
        long t25980 = i25978;
        long c259790 = t25980 / 20; t25980 %= 20;
        long c259791 = t25980 / 2; t25980 %= 2;
        long c259792 = t25980;
        r1225[c259790 * 10 + c259791 * 1] = max32(r1225[c259790 * 10 + c259791 * 1], r1224[i25978]);
    }
    /* sub [sub] -> r1227 */
    for (long i25981 = 0; i25981 < 10; ++i25981) {
        r1227[i25981] = sub32(r1225[i25981], r1226[0]);
    }
    /* loop [scan] -> r1243 */
    memcpy(r1228, r1224, sizeof(int32_t) * 20);
    memcpy(r1229, r1226, sizeof(int32_t) * 1);
    memcpy(r1230, r39, sizeof(int32_t) * 1);
    memcpy(r1231, r1227, sizeof(int32_t) * 10);
    memcpy(r1232, r1225, sizeof(int32_t) * 10);
    for (long t25982 = 0; t25982 < 8; ++t25982) {
        /* add [add] -> r1233 */
        for (long i26983 = 0; i26983 < 1; ++i26983) {
            r1233[i26983] = add32(r1230[0], r30[0]);
        }
        /* add [add] -> r1234 */
        for (long i26984 = 0; i26984 < 10; ++i26984) {
            r1234[i26984] = add32(r1231[i26984], r1232[i26984]);
        }
        /* shra [shift_right_arithmetic] -> r1235 */
        for (long i26985 = 0; i26985 < 10; ++i26985) {
            r1235[i26985] = asr32(r1234[i26985], 1);
        }
        /* broadcast [broadcast_in_dim] -> r1236 */
        for (long i26986 = 0; i26986 < 10; ++i26986) {
            long t26988 = i26986;
            long c269870 = t26988 / 10; t26988 %= 10;
            long c269871 = t26988 / 1; t26988 %= 1;
            long c269872 = t26988;
            r1236[i26986] = r1235[c269871 * 1];
        }
        /* sub [sub] -> r1237 */
        for (long i26989 = 0; i26989 < 20; ++i26989) {
            long t26991 = i26989;
            long c269900 = t26991 / 20; t26991 %= 20;
            long c269901 = t26991 / 2; t26991 %= 2;
            long c269902 = t26991;
            r1237[i26989] = sub32(r1228[c269901 * 2 + c269902 * 1], r1236[c269901 * 1]);
        }
        /* max [max] -> r1238 */
        for (long i26992 = 0; i26992 < 20; ++i26992) {
            r1238[i26992] = max32(r1237[i26992], r39[0]);
        }
        /* reduce_sum [reduce_sum] -> r1239 */
        for (long i26993 = 0; i26993 < 10; ++i26993) {
            r1239[i26993] = 0;
        }
        for (long i26994 = 0; i26994 < 20; ++i26994) {
            long t26996 = i26994;
            long c269950 = t26996 / 20; t26996 %= 20;
            long c269951 = t26996 / 2; t26996 %= 2;
            long c269952 = t26996;
            r1239[c269950 * 10 + c269951 * 1] = add32(r1239[c269950 * 10 + c269951 * 1], r1238[i26994]);
        }
        /* gt [gt] -> r1240 */
        for (long i26997 = 0; i26997 < 10; ++i26997) {
            r1240[i26997] = r1239[i26997] > r1229[0] ? 1 : 0;
        }
        /* select_n [select_n] -> r1241 */
        for (long i26998 = 0; i26998 < 10; ++i26998) {
            r1241[i26998] = r1240[i26998] == 0 ? r1231[i26998] : (r1235[i26998]);
        }
        /* select_n [select_n] -> r1242 */
        for (long i26999 = 0; i26999 < 10; ++i26999) {
            r1242[i26999] = r1240[i26999] == 0 ? r1235[i26999] : (r1232[i26999]);
        }
        memcpy(r1230, r1233, sizeof(int32_t) * 1);
        memcpy(r1231, r1241, sizeof(int32_t) * 10);
        memcpy(r1232, r1242, sizeof(int32_t) * 10);
    }
    memcpy(r1243, r1230, sizeof(int32_t) * 1);
    memcpy(r1244, r1231, sizeof(int32_t) * 10);
    memcpy(r1245, r1232, sizeof(int32_t) * 10);
    /* sub [sub] -> r1246 */
    for (long i27000 = 0; i27000 < 10; ++i27000) {
        r1246[i27000] = sub32(r1185[i27000], r1245[i27000]);
    }
    /* max [max] -> r1247 */
    for (long i27001 = 0; i27001 < 10; ++i27001) {
        r1247[i27001] = max32(r1246[i27001], r39[0]);
    }
    /* sub [sub] -> r1248 */
    for (long i27002 = 0; i27002 < 10; ++i27002) {
        r1248[i27002] = sub32(r1221[i27002], r1245[i27002]);
    }
    /* max [max] -> r1249 */
    for (long i27003 = 0; i27003 < 10; ++i27003) {
        r1249[i27003] = max32(r1248[i27003], r39[0]);
    }
    /* sub [sub] -> r1250 */
    for (long i27004 = 0; i27004 < 10; ++i27004) {
        r1250[i27004] = sub32(r1247[i27004], r1249[i27004]);
    }
}

int main(int argc, char **argv) {
    if (argc != 3) { fprintf(stderr, "usage: %s in.bin out.bin\n", argv[0]); return 2; }
    FILE *fi = fopen(argv[1], "rb");
    if (!fi) { perror("in"); return 2; }
    if (fread(r0, sizeof(int32_t), 15, fi) != 15) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r1, sizeof(int32_t), 15, fi) != 15) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r2, sizeof(int32_t), 15, fi) != 15) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r3, sizeof(int32_t), 15, fi) != 15) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r4, sizeof(int32_t), 15, fi) != 15) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r5, sizeof(int32_t), 15, fi) != 15) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r6, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r7, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r8, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r9, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r10, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r11, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r12, sizeof(int32_t), 30, fi) != 30) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r13, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r14, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r15, sizeof(uint8_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r16, sizeof(int32_t), 160, fi) != 160) { fprintf(stderr, "short read\n"); return 2; }
    if (fread(r17, sizeof(int32_t), 1, fi) != 1) { fprintf(stderr, "short read\n"); return 2; }
    fclose(fi);
    program_run();
    FILE *fo = fopen(argv[2], "wb");
    if (!fo) { perror("out"); return 2; }
    fwrite(r130, sizeof(int32_t), 15, fo);
    fwrite(r326, sizeof(int32_t), 15, fo);
    fwrite(r521, sizeof(int32_t), 15, fo);
    fwrite(r716, sizeof(int32_t), 15, fo);
    fwrite(r911, sizeof(int32_t), 15, fo);
    fwrite(r1106, sizeof(int32_t), 15, fo);
    fwrite(r131, sizeof(int32_t), 1, fo);
    fwrite(r327, sizeof(int32_t), 1, fo);
    fwrite(r522, sizeof(int32_t), 1, fo);
    fwrite(r717, sizeof(int32_t), 1, fo);
    fwrite(r912, sizeof(int32_t), 1, fo);
    fwrite(r1107, sizeof(int32_t), 1, fo);
    fwrite(r1109, sizeof(int32_t), 30, fo);
    fwrite(r28, sizeof(int32_t), 1, fo);
    fwrite(r1110, sizeof(int32_t), 1, fo);
    fwrite(r15, sizeof(uint8_t), 1, fo);
    fwrite(r1250, sizeof(int32_t), 10, fo);
    fwrite(r1144, sizeof(int32_t), 30, fo);
    fclose(fo);
    return 0;
}
