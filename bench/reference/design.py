"""Filter design of the multirate MP filter bank (paper Fig. 3, Table III).

Octave o covers [nyq / 2**(o+1), nyq / 2**o] at the rate fs / 2**o, with
``filters_per_octave`` band-pass filters whose edges split the octave
evenly. Each band-pass is the difference of two windowed sincs, Hamming
windowed, normalised to unit gain at its centre frequency. Each /2 stage
has a Hamming-windowed sinc low-pass with its cutoff at a quarter of the
stage's rate, normalised to unit DC gain. Taps are designed in float64
and stored as float32.
"""

from __future__ import annotations

import numpy as np


def hamming(m: int) -> np.ndarray:
    k = np.arange(m)
    return 0.54 - 0.46 * np.cos(2 * np.pi * k / (m - 1))


def lowpass(m: int, cutoff: float, rate: float) -> np.ndarray:
    fc = cutoff / rate
    t = np.arange(m) - (m - 1) / 2.0
    h = 2 * fc * np.sinc(2 * fc * t) * hamming(m)
    return (h / h.sum()).astype(np.float32)


def bandpass(m: int, f_lo: float, f_hi: float, rate: float) -> np.ndarray:
    t = np.arange(m) - (m - 1) / 2.0
    h = (2 * (f_hi / rate) * np.sinc(2 * (f_hi / rate) * t)
         - 2 * (f_lo / rate) * np.sinc(2 * (f_lo / rate) * t))
    h = h * hamming(m)
    w = 2 * np.pi * ((f_lo + f_hi) / 2.0) / rate
    gain = np.abs(np.sum(h * np.exp(-1j * w * np.arange(m))))
    return (h / max(gain, 1e-6)).astype(np.float32)


def taps(fb: dict) -> tuple:
    """``(bp, lp)``: per octave an (F, M) float32 band-pass bank, per /2
    stage an (M_lp,) float32 low-pass."""
    fs, octaves = float(fb["fs"]), int(fb["num_octaves"])
    per, m_bp, m_lp = (int(fb["filters_per_octave"]), int(fb["bp_taps"]),
                       int(fb["lp_taps"]))
    nyq = fs / 2.0
    bp = []
    for o in range(octaves):
        rate = fs / 2 ** o
        edges = np.linspace(nyq / 2 ** (o + 1), nyq / 2 ** o, per + 1)
        bp.append(np.stack([bandpass(m_bp, edges[p], edges[p + 1], rate)
                            for p in range(per)]))
    lp = [lowpass(m_lp, (fs / 2 ** o) / 4.0, fs / 2 ** o)
          for o in range(octaves - 1)]
    return bp, lp
