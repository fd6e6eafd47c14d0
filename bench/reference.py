"""Plain reference of a folded network, its lower-precision control, and
the comparison that decides a run's ``correct``.

The reference is the paper's folded inference written out in numpy: hard-
quantize the inputs to codes, then per layer gather each unit's fan-in
codes (the mapping, or the contiguous slice of an assemble layer), pack
them into an address (first input in the most significant bits) and read
the unit's table; dequantize the last layer's codes into logits.  It
imports nothing of the program and reads only the configuration file and
the arrays ``bench/lutnet.py`` drew.

The control is the same reference with every table held one integer type
narrower than the type its widest code needs (int8 -> int4 for mnist,
int16 -> int8 for jsc_openml): the step a change that packs tables tighter
would take.  Wrapped entries change answers, so the control must fail.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from bench.lutnet import layer_shapes

BLOCK_ROWS = 1024
# a comparison that cannot be made (wrong shape, non-finite logits) reads
# this, far above any limit; JSON has no infinity
UNREADABLE = 1e30


def _qrange(bits: int, signed: bool):
    return (-(2 ** (bits - 1)), 2 ** (bits - 1) - 1) if signed \
        else (0, 2 ** bits - 1)


def scale(log_scale: float) -> np.float32:
    """A quantizer's step from its log-scale, in float32."""
    return np.float32(np.exp(np.float32(log_scale)))


def stored_bits(cfg: dict) -> int:
    """Bits of the narrowest signed integer type that holds every code."""
    widest = max(int(l["bits"]) for l in cfg["layers"])
    return next(b for b in (8, 16, 32) if widest <= b - 1)


def narrow(tables: Sequence[np.ndarray], bits: int) -> List[np.ndarray]:
    """Tables as a signed ``bits``-bit integer would hold them."""
    half = 2 ** (bits - 1)
    return [((np.asarray(t, np.int64) + half) % (2 * half)) - half
            for t in tables]


def control_tables(cfg: dict, tables: Sequence[np.ndarray]
                   ) -> List[np.ndarray]:
    """The control's tables: one integer type below the stored one."""
    return narrow(tables, stored_bits(cfg) // 2)


def forward(cfg: dict, tables: Sequence[np.ndarray],
            mappings: Sequence[Optional[np.ndarray]], x: np.ndarray):
    """Codes ``[n, n_out]`` int32 and logits ``[n, n_out]`` float32 for
    float rows ``x``, computed in blocks of ``BLOCK_ROWS`` rows."""
    shapes = layer_shapes(cfg)
    flat = [np.asarray(t, np.int32).ravel() for t in tables]
    lo, hi = _qrange(int(cfg["input_bits"]), bool(cfg["input_signed"]))
    s_in = scale(cfg["in_log_scale"])
    out_lo, _ = _qrange(shapes[-1]["bits"], True)
    s_out = scale(cfg["out_log_scale"])
    codes_out = []
    for r0 in range(0, len(x), BLOCK_ROWS):
        xb = np.asarray(x[r0:r0 + BLOCK_ROWS], np.float32)
        h = np.clip(np.round(xb / s_in), lo, hi).astype(np.int32) - lo
        for s, tab, mp in zip(shapes, flat, mappings):
            units, fan_in = s["units"], s["fan_in"]
            if s["assemble"]:
                ci = h.reshape(len(h), units, fan_in)
            else:
                ci = h[:, np.asarray(mp)]                  # [b, units, F]
            weights = (1 << (s["in_bits"] * np.arange(fan_in - 1, -1, -1))
                       ).astype(np.int32)
            addr = (ci * weights).sum(-1, dtype=np.int32) & (s["entries"] - 1)
            h = tab[np.arange(units, dtype=np.int32) * s["entries"] + addr]
        codes_out.append(h.astype(np.int32))
    codes = np.concatenate(codes_out) if codes_out else np.zeros(
        (0, shapes[-1]["units"]), np.int32)
    logits = (codes.astype(np.float32) + np.float32(out_lo)) * s_out
    return codes, logits


def compare(cfg: dict, codes: np.ndarray, logits: np.ndarray,
            ref_codes: np.ndarray, ref_logits: np.ndarray
            ) -> Dict[str, float]:
    """The numbers a run is judged on.

    ``rows_wrong``: rows whose output codes differ from the reference.
    ``logit_gap``: the widest logit difference, in output quantization
    steps (a wrong code is one step or more)."""
    if codes.shape != ref_codes.shape or logits.shape != ref_logits.shape:
        return {"rows_wrong": float(len(ref_codes)), "logit_gap": UNREADABLE}
    wrong = int((codes != ref_codes).any(axis=-1).sum())
    gap = (float(np.max(np.abs(logits.astype(np.float64) - ref_logits)))
           / float(scale(cfg["out_log_scale"])) if len(codes) else 0.0)
    return {"rows_wrong": float(wrong),
            "logit_gap": gap if np.isfinite(gap) else UNREADABLE}
