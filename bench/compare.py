"""The comparison that decides ``correct``.

What is compared is what the timed run served: for a sample of the
streams open when the window closed, drawn from the seed and holding the
longest, the last ``FeedResult`` each was served in the window and the
accumulator registers the session step left in its slot. The plain
reference (``bench/reference``) recomputes both from the audio that
stream was fed, in one shot.

Numbers, each against the limit its configuration file gives:

* fixed numerics, exact (limit 0):
  ``acc_mismatch``: accumulator registers that differ from the reference;
  ``decision_mismatch``: streams whose served label or confidence differs
  from the reference's argmax class and its value.
* float numerics:
  ``acc_rel_gap``: the largest |acc - ref| / ref over the sampled
  registers;
  ``p_gap``: the largest of (ref's best score - ref's score of the served
  label) and |served confidence - ref's score of the served label).
"""

from __future__ import annotations

import math

import numpy as np

from bench.reference import fixed_ref, float_ref

PAD = 1 << 17           # reference lengths round up to this many samples


def sample(loop, seed: int, count: int) -> list:
    """Session ids to check: the longest open stream that was fed, and
    ``count - 1`` more drawn from the seed."""
    from bench import loadgen
    fed = [(sid, n) for sid, n in loop.streams() if n > 0]
    longest = max(fed, key=lambda x: x[1])[0]
    rest = [sid for sid, _ in fed if sid != longest]
    g = loadgen.rng(seed, "check")
    pick = g.choice(len(rest), size=min(count - 1, len(rest)),
                    replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def batch(audios: list) -> tuple:
    """Zero-padded (B, N) audio, N a multiple of ``PAD``, and lengths."""
    lengths = np.asarray([a.shape[0] for a in audios], np.int64)
    n = int(math.ceil(lengths.max() / PAD) * PAD)
    out = np.zeros((len(audios), n), np.float32)
    for b, a in enumerate(audios):
        out[b, :a.shape[0]] = a
    return out, lengths


def reference(cfg: dict, w: dict, audio: np.ndarray, lengths: np.ndarray,
              control: bool = False) -> dict:
    """The reference's accumulators and class scores as real values, at
    the configuration's precision, or the control's lower one."""
    if cfg["numerics"] == "fixed":
        bits = int(cfg["check"]["control_bits"]) if control else 8
        p = fixed_ref.plan(cfg, w, bits=bits)
        r = fixed_ref.run(p, audio, lengths)
        return {"acc": r["acc"] * math.ldexp(1.0, p.acc_exp),
                "p": r["p"] * math.ldexp(1.0, r["p_exp"]),
                "acc_exp": p.acc_exp}
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if control else jnp.float32
    return float_ref.run(cfg, w, audio, lengths, dtype=dtype)


def numbers(cfg: dict, served: dict, ref: dict) -> dict:
    """The compared numbers. ``served`` holds ``acc`` (B, P) real values
    and per stream ``label`` and ``confidence``."""
    acc, rp = np.asarray(served["acc"], np.float64), ref["p"]
    labels = np.asarray(served["label"])
    conf = np.asarray(served["confidence"], np.float64)
    at = rp[np.arange(len(labels)), labels].astype(np.float64)
    if cfg["numerics"] == "fixed":
        best = np.argmax(rp, axis=1)
        return {
            "acc_mismatch": int(np.sum(acc != ref["acc"])),
            "decision_mismatch": int(np.sum((labels != best) | (conf != at))),
        }
    racc = ref["acc"].astype(np.float64)
    return {
        "acc_rel_gap": float(np.max(np.abs(acc - racc) / np.abs(racc))),
        "p_gap": float(max(np.max(rp.max(axis=1) - at),
                           np.max(np.abs(conf - at)))),
    }


def served_of(cfg: dict, acc_rows: np.ndarray, results: list,
              ref: dict) -> dict:
    """The program's served values as real numbers: fixed accumulator
    codes are read on the reference's grid."""
    acc = np.asarray(acc_rows, np.float64)
    if cfg["numerics"] == "fixed":
        acc = acc * math.ldexp(1.0, ref["acc_exp"])
    return {"acc": acc, "label": [r.label for r in results],
            "confidence": [r.confidence for r in results]}


def control_served(ref_ctl: dict) -> dict:
    """What the control, put in the program's place, would have served."""
    labels = np.argmax(ref_ctl["p"], axis=1)
    return {"acc": ref_ctl["acc"], "label": labels,
            "confidence": ref_ctl["p"][np.arange(len(labels)), labels]}


def limits(cfg: dict) -> dict:
    return {k: float(v) for k, v in cfg["check"]["limits"].items()}


def verdict(nums: dict, lim: dict) -> bool:
    return all(nums[k] <= lim[k] for k in lim)
