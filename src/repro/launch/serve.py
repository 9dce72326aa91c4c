"""Serving launcher: LLM decode AND acoustic stream sessions, one CLI.

LLM decode (batched autoregressive, sharded KV cache):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --batch 4 --prompt-len 16 --gen 32

Request flow: a batch of prompts is prefetched (prefill via the forward
pass teacher-forcing the prompt tokens through decode_step slots), then
tokens are generated one step at a time with the jitted serve_step. The
cache is donated across steps (no per-token reallocation).

Acoustic stream serving (the paper's deployment: only classified data
leaves the device):

    PYTHONPATH=src python -m repro.launch.serve --arch esc10-mp --smoke \
        --streams 16 --chunk 160 --rounds 25

Many logical sensor streams are multiplexed onto one slot-batched
``StreamServer``: each round feeds one sensor packet per stream, and all
resident streams advance in ONE compiled donated-state step.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_arch, get_smoke

ACOUSTIC_ARCH = "esc10-mp"


def _serve_acoustic(args):
    from repro.configs.esc10_mp import make_pipeline
    from repro.serving import StreamRouter, StreamServer

    pipe = make_pipeline(smoke=args.smoke, seed=args.seed,
                         stream_impl=args.stream_impl,
                         numerics=args.numerics,
                         fixed_amax=args.fixed_amax)
    fs = pipe.config.fs
    # chunk bounds must be powers of two (the server's bucket-ladder
    # contract): round the packet length up to the bucket it pads into
    max_chunk = max(16, 1 << (args.chunk - 1).bit_length())
    if args.shards > 1:
        server = StreamRouter(pipe, num_shards=args.shards,
                              capacity=args.streams, max_chunk=max_chunk)
    else:
        server = StreamServer(pipe, capacity=args.streams,
                              max_chunk=max_chunk)
    rng = np.random.default_rng(args.seed)
    ids = [f"mic-{i:03d}" for i in range(args.streams)]
    for sid in ids:
        server.open(sid)
    # synthetic sensors: band-limited-ish noise, one phase offset per stream
    audio = rng.standard_normal(
        (args.streams, args.rounds * args.chunk)).astype(np.float32)

    callers = max(1, min(4, args.streams))
    t0 = time.time()
    results = []
    for r in range(args.rounds):
        sl = slice(r * args.chunk, (r + 1) * args.chunk)
        reqs = [(sid, audio[i, sl]) for i, sid in enumerate(ids)]
        if args.use_async:
            # G independent callers coalesce into shared waves; one
            # drain resolves the round (decisions bitwise == sync feed)
            tickets = [server.submit(reqs[g::callers])
                       for g in range(callers)]
            server.drain()
            results = [res for t in tickets for res in t.results]
        else:
            results = server.feed(reqs)
    state = server.shards[0].state if args.shards > 1 else server.state
    jax.block_until_ready(state.acc)
    wall = time.time() - t0
    fed = args.streams * args.rounds
    dev = jax.devices()[0]
    print(f"device={dev.platform}:{dev.device_kind} "
          f"x{len(jax.devices())} "
          f"arch={ACOUSTIC_ARCH} streams={args.streams} "
          f"chunk={args.chunk} ({args.chunk / fs * 1e3:.0f} ms) "
          f"rounds={args.rounds} shards={args.shards} "
          f"async={args.use_async} "
          f"numerics={pipe.config.numerics}")  # float engine vs the fixed-
    # point hardware twin (stats() repeats it so operators can tell a
    # deployment preview from the float path mid-flight)
    print(f"served {fed} chunks in {wall*1e3:.0f} ms "
          f"({fed / max(wall, 1e-9):.0f} chunks/s, "
          f"{fed * args.chunk / max(wall, 1e-9) / 1e6:.2f} Msamples/s, "
          f"stats={server.stats()})")
    for res in results[:4]:
        print(f"  {res.session_id}: label={res.label} "
              f"confidence={res.confidence:+.3f} "
              f"samples={res.samples_seen}")
    return results


def _serve_decode(args):
    from repro.distributed.steps import make_serve_step
    from repro.models import transformer as T

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    assert cfg.supports_decode, f"{cfg.name} is encoder-only"
    assert not cfg.vlm_patches, "serve demo uses text-only prompts"

    key = jax.random.PRNGKey(args.seed)
    params = T.init(cfg, key)
    B = args.batch
    total = args.prompt_len + args.gen
    cache_len = total if cfg.sliding_window is None \
        else min(total, cfg.sliding_window)
    cache = T.init_cache(cfg, B, cache_len)
    serve_step = jax.jit(make_serve_step(cfg, args.temperature),
                         donate_argnums=(2,), static_argnums=())

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size, (B, args.prompt_len))

    # prefill: feed prompt tokens through decode slots (teacher forcing)
    t0 = time.time()
    for i in range(args.prompt_len):
        pos = jnp.full((B,), i, jnp.int32)
        nxt, _, cache = serve_step(params, jnp.asarray(prompts[:, i:i+1],
                                                       jnp.int32), cache, pos)
    prefill_s = time.time() - t0

    # generate
    t0 = time.time()
    tok = nxt
    gen = []
    for i in range(args.gen):
        pos = jnp.full((B,), args.prompt_len + i, jnp.int32)
        key, sk = jax.random.split(key)
        tok, logits, cache = serve_step(params, tok, cache, pos, sk)
        gen.append(np.asarray(tok))
    gen_s = time.time() - t0
    gen_arr = np.concatenate(gen, axis=1)
    print(f"arch={cfg.name} batch={B} prompt={args.prompt_len} gen={args.gen}")
    print(f"prefill {prefill_s*1e3:.0f} ms, decode {gen_s*1e3:.0f} ms "
          f"({args.gen*B/max(gen_s,1e-9):.1f} tok/s)")
    print("sample generation:", gen_arr[0][:16].tolist())
    return gen_arr


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCH_IDS) + [ACOUSTIC_ARCH],
                    required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # LLM decode knobs
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    # acoustic stream knobs
    ap.add_argument("--streams", type=int, default=16,
                    help="esc10-mp: concurrent sensor sessions (slots)")
    ap.add_argument("--chunk", type=int, default=160,
                    help="esc10-mp: sensor packet length in samples")
    ap.add_argument("--rounds", type=int, default=25,
                    help="esc10-mp: packets fed per stream")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="esc10-mp: feed through the coalescing "
                         "submit()/drain() pipeline (4 virtual callers "
                         "per round) instead of synchronous feed() — "
                         "decisions are bit-for-bit identical")
    ap.add_argument("--shards", type=int, default=1,
                    help="esc10-mp: >1 serves through a StreamRouter "
                         "with this many StreamServer shards (stream id "
                         "-> crc32 shard; shared compiled step)")
    ap.add_argument("--stream-impl", choices=["xla", "pallas"],
                    default="xla",
                    help="esc10-mp: session-step hot path — 'pallas' runs "
                         "the stateful fir_mp_stream kernel (VMEM-carried "
                         "delay lines; interpret mode off-TPU)")
    ap.add_argument("--numerics", choices=["float", "fixed"],
                    default="float",
                    help="esc10-mp: 'fixed' serves the bit-true int32 "
                         "hardware twin — integer session registers, "
                         "streamed decisions bit-for-bit equal to one-shot "
                         "inference, through either --stream-impl "
                         "('pallas' runs the VMEM-resident int kernel "
                         "fir_mp_stream_q, bit-identical to 'xla')")
    ap.add_argument("--fixed-amax", type=float, default=None,
                    help="esc10-mp: ADC full-scale for --numerics fixed "
                         "(default: the config's static 1.0; the synthetic "
                         "sensors here peak around 4, so pass ~4.0 to "
                         "avoid saturating the demo)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.arch == ACOUSTIC_ARCH:
        return _serve_acoustic(args)
    return _serve_decode(args)


if __name__ == "__main__":
    main()
