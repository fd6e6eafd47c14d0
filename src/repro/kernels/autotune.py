"""Roofline-driven autotuner for the fused L-LUT cascade kernels.

The fused cascade (`kernels.lut_cascade`) has three knobs that matter for
throughput — which implementation runs (compiled Pallas vs the pure-jnp
flat-gather path), how the Pallas kernel tiles (``mode`` resident vs
streamed, ``block_b`` batch tile, ``unit_tile`` streamed tile width) — and
the right answers depend on (table size, beta, device).  This module owns
that decision:

  * :class:`KernelTuning` — the chosen knobs, serialized into
    ``ExecutionPlan.meta["tuning"]`` by the fused backend so the choice
    survives ``save``/``load`` and mesh placement (docs/KERNELS.md §5).
  * :func:`pick_tuning` — the *model-driven* tuner: a per-candidate
    roofline (compute time vs memory-movement time against the device's
    peak flops / HBM bandwidth, VMEM-feasibility filtered) picked without
    running anything.  This is what planning uses by default.
  * :func:`measure_tuning` — the *measurement-driven* tuner: times a
    caller-supplied runner over the candidate grid and returns the fastest
    (``source="measured"``).  ``FusedCascadeBackend.autotune_plan`` wires
    it to a real plan; docs/PERF_TUNING.md shows the workflow.
  * :func:`roofline_candidates` / :func:`choice_table` — the modeled
    candidate grid, as data: ``benchmarks/roofline.py --lut`` prints it
    and the nightly CI job uploads :func:`choice_table` over every paper
    task as an artifact.

The model is deliberately small: lookup tables admit no data reuse beyond
what fits in VMEM, so the only real questions are "do the packed buffers
fit?" (picks resident vs streamed) and "how big a batch tile keeps the
kernel inside the VMEM budget, and its select scan inside the tile size
it runs fastest at?" (picks ``block_b``).  Both are answered against
:data:`DEVICE_MODELS`, keyed by JAX's ``device_kind``, with the VMEM the
compiled kernel allocates (``lut_cascade.vmem_bytes``).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# Peak rates and the kernel VMEM budget per device, keyed by JAX's
# ``device_kind``.  A device missing here is an error (:func:`device_kind`):
# no peak or VMEM size is ever assumed.
DEVICE_MODELS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "peak_flops": 197e12,        # bf16
        "peak_int8_ops": 393e12,
        "hbm_bw": 819e9,
        "hbm_bytes": 16e9,
        # the scoped-VMEM limit the v5e compiler enforces per kernel
        # ("Scoped allocation with size ... and limit 16.00M"); the chip
        # has more VMEM, but a kernel gets this much unless it asks
        "vmem_bytes": 16 * 2**20,
        # the select scan's [block_b, 128] int32 address and carry tiles,
        # at most: bulk rows/s at 64/128/256/512/1024-row tiles, mnist
        # 1.63/1.93/1.66/1.42M, jsc_openml 11.3/12.4/8.9/7.2/6.1M
        # (PERF.md).  Past 128 rows the scan slows, most likely as its
        # tiles spill out of the vector registers.
        "scan_bytes": 2 * 128 * 128 * 4,
        "source": "Google Cloud documentation, \"TPU v5e\" (peaks, HBM); "
                  "vmem_bytes: Mosaic's scoped-VMEM limit on v5e; "
                  "scan_bytes: measured on a v5e",
    },
    "cpu": {
        # the host streaming from LLC/DRAM; coarse on purpose: the model
        # only ranks candidates there, and the CPU serves the XLA path
        "peak_flops": 2e11,
        "hbm_bw": 4e10,
        "vmem_bytes": 8 * 2**20,
        "scan_bytes": None,     # the host runs the XLA path, no scan
        "source": "rough host model, ranking only (no measurement)",
    },
}

BLOCK_B_CANDIDATES = (64, 128, 256, 512, 1024)
# One lane tile per streamed phase: compiled for a v5e, every wider tile
# needs more VMEM than the kernel model counts (at mnist width and
# block_b 256: 20.6 MiB at 256 lanes against 8.8 MiB at 128), so the
# model offers none.
UNIT_TILE_CANDIDATES = (128,)


@dataclasses.dataclass(frozen=True)
class KernelTuning:
    """One fused-cascade tuning choice, persisted in the ExecutionPlan.

    ``impl`` ``None`` means auto: compiled Pallas on TPU, the jnp
    flat-gather path wherever Pallas would run interpreted (``ops``
    resolves it per process, so one artifact serves both device kinds).
    ``source`` records provenance: ``default`` (planning), ``roofline``
    (modeled) or ``measured`` (timed on this host).  ``device_kind`` is
    the device the choice was made for; a plan run on another kind is
    re-picked (:func:`for_device`).
    """

    impl: Optional[str] = None          # None=auto | "xla" | "pallas"
    mode: str = "resident"              # "resident" | "streamed"
    block_b: int = 256
    unit_tile: int = 128
    table_dtype: Optional[str] = None   # narrowest that fits when None
    source: str = "default"
    device_kind: Optional[str] = None

    def to_meta(self) -> Dict[str, Any]:
        """JSON-serializable form for ``ExecutionPlan.meta['tuning']``."""
        return dataclasses.asdict(self)

    @classmethod
    def from_meta(cls, meta: Optional[Dict[str, Any]]) -> "KernelTuning":
        """Rebuild from plan meta; unknown keys (from a newer schema) are
        dropped rather than erroring so old code can run newer plans."""
        if not meta:
            return cls()
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in meta.items() if k in fields})


def device_kind(kind: Optional[str] = None) -> str:
    """Validate a ``device_kind`` against :data:`DEVICE_MODELS` (default:
    this process's first device).  Raises for a device with no model."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    if kind not in DEVICE_MODELS:
        raise ValueError(
            f"no device model for device_kind {kind!r}; known: "
            f"{sorted(DEVICE_MODELS)} (add a row with its published peaks "
            "and its compiler's VMEM limit to autotune.DEVICE_MODELS)")
    return kind


def config_layers(cfg) -> List[Tuple[int, ...]]:
    """The fused plan's v2 layer tuples of an ``AssembleConfig`` (shapes
    only: what the model needs, without folding any tables)."""
    layers, off = [], 0
    for l, spec in enumerate(cfg.layers):
        layers.append((cfg.prev_width(l), spec.units,
                       2 ** (cfg.in_bits(l) * spec.fan_in), off,
                       spec.fan_in, cfg.in_bits(l), int(spec.assemble)))
        off += spec.units
    return layers


# ---------------------------------------------------------------------------
# the roofline model
# ---------------------------------------------------------------------------

def roofline_candidates(layers: Sequence[Sequence[int]], *,
                        batch: int = 4096,
                        device: Optional[str] = None) -> List[Dict[str, Any]]:
    """The modeled candidate grid: one row per (mode, block_b[, unit_tile])
    with compute time, memory time, the binding roof, and VMEM feasibility
    under the device's kernel VMEM budget.

    Rows are plain dicts so ``benchmarks/roofline.py`` can print them and
    the nightly choice-table artifact can serialize them verbatim.
    """
    # local import keeps this module importable without pulling kernels in
    from repro.kernels.lut_cascade import (LANE, cascade_bytes,
                                           cascade_flops, vmem_bytes)
    dev = device_kind(device)
    m = DEVICE_MODELS[dev]
    flops = cascade_flops(layers, batch)
    rows: List[Dict[str, Any]] = []
    for mode in ("resident", "streamed"):
        for block_b in BLOCK_B_CANDIDATES:
            for unit_tile in (UNIT_TILE_CANDIDATES if mode == "streamed"
                              else (None,)):
                tile = {} if unit_tile is None else {"unit_tile": unit_tile}
                vmem = vmem_bytes(layers, mode=mode, block_b=block_b,
                                  **tile)
                byts = cascade_bytes(layers, batch, mode=mode,
                                     block_b=block_b, **tile)
                t_comp = flops / m["peak_flops"]
                t_mem = byts / m["hbm_bw"]
                rows.append({
                    "device": dev, "mode": mode, "block_b": block_b,
                    "unit_tile": unit_tile,
                    "flops": flops, "bytes": byts,
                    "t_compute_us": round(t_comp * 1e6, 3),
                    "t_memory_us": round(t_mem * 1e6, 3),
                    "bound": "compute" if t_comp >= t_mem else "memory",
                    "t_us": round(max(t_comp, t_mem) * 1e6, 3),
                    "rows_per_s": round(batch / max(t_comp, t_mem), 1),
                    "vmem_bytes": vmem,
                    "fits_vmem": vmem <= m["vmem_bytes"],
                    "fits_scan": m["scan_bytes"] is None
                    or 2 * block_b * LANE * 4 <= m["scan_bytes"],
                })
    return rows


def _tuning(row: Dict[str, Any], **kw) -> KernelTuning:
    return KernelTuning(mode=row["mode"], block_b=row["block_b"],
                        unit_tile=row["unit_tile"] or UNIT_TILE_CANDIDATES[0],
                        device_kind=row["device"], **kw)


def pick_tuning(layers: Sequence[Sequence[int]], *, batch: int = 4096,
                device: Optional[str] = None,
                table_dtype: Optional[str] = None) -> KernelTuning:
    """Model-driven choice: the fastest VMEM-feasible roofline candidate
    whose select-scan tiles fit the device's ``scan_bytes`` (any
    VMEM-feasible one when none does).

    Ties break toward resident mode (no re-streaming) and larger batch
    tiles (fewer grid steps).  ``impl`` stays ``None`` (auto) so the same
    persisted plan runs compiled Pallas on TPU and the jnp flat-gather
    path on interpret-mode hosts.  When nothing fits, the smallest
    streamed tiling is returned, and compiling it fails loudly.
    """
    rows = roofline_candidates(layers, batch=batch, device=device)
    fits = [r for r in rows if r["fits_vmem"]]
    fits = [r for r in fits if r["fits_scan"]] or fits
    if not fits:
        return _tuning(min(rows, key=lambda r: r["vmem_bytes"]),
                       table_dtype=table_dtype, source="roofline")
    fits.sort(key=lambda r: (r["t_us"],
                             0 if r["mode"] == "resident" else 1,
                             -r["block_b"]))
    return _tuning(fits[0], table_dtype=table_dtype, source="roofline")


def default_tuning(layers: Sequence[Sequence[int]], *,
                   table_dtype: Optional[str] = None) -> KernelTuning:
    """The tuning stamped on plans that never ran the tuner (fresh plans,
    v1 plans migrated across the schema bump): the roofline pick for the
    current device, ``source="default"`` so tooling can tell it apart
    from an explicit tuner run."""
    t = pick_tuning(layers, table_dtype=table_dtype)
    return dataclasses.replace(t, source="default")


def for_device(tuning: KernelTuning, layers: Sequence[Sequence[int]], *,
               table_dtype: Optional[str] = None) -> KernelTuning:
    """``tuning`` when it was picked for this process's device kind, else
    the roofline pick for this device (a plan tuned on a CPU host and
    served from a TPU, or the other way round, is re-picked, never run
    with the other device's tiles)."""
    kind = device_kind()
    if tuning.device_kind == kind:
        return tuning
    return pick_tuning(layers, device=kind, table_dtype=table_dtype)


# ---------------------------------------------------------------------------
# measurement-driven tuning
# ---------------------------------------------------------------------------

def measure_tuning(run_factory: Callable[[KernelTuning], Callable[[], Any]],
                   candidates: Sequence[KernelTuning], *,
                   reps: int = 3) -> Tuple[KernelTuning, List[Dict[str, Any]]]:
    """Time each candidate and return (fastest, per-candidate report).

    ``run_factory(tuning)`` returns a nullary callable that executes one
    full cascade pass with that tuning and blocks until done (the caller
    owns data/jit setup; the first call per candidate is discarded as
    compile warm-up).  Reps are interleaved across candidates so a slow
    host phase hits all of them equally; best-of is kept (noise on a
    loaded host is one-sided).
    """
    if not candidates:
        raise ValueError("measure_tuning: empty candidate list")
    runners = [run_factory(t) for t in candidates]
    for r in runners:
        r()  # warm-up / compile, excluded from timing
    best = [math.inf] * len(candidates)
    for _ in range(max(1, reps)):
        for i, r in enumerate(runners):
            t0 = time.perf_counter()
            r()
            best[i] = min(best[i], time.perf_counter() - t0)
    report = [{"tuning": t.to_meta(), "best_s": round(b, 6)}
              for t, b in zip(candidates, best)]
    winner = dataclasses.replace(
        candidates[min(range(len(best)), key=best.__getitem__)],
        source="measured")
    return winner, report


def measurement_grid(layers: Sequence[Sequence[int]], *,
                     table_dtype: Optional[str] = None,
                     max_candidates: int = 6) -> List[KernelTuning]:
    """A small measurement grid seeded by the roofline ranking: the model
    orders the VMEM-feasible candidates, measurement confirms the top few
    (model-guided search instead of brute force)."""
    rows = [r for r in roofline_candidates(layers) if r["fits_vmem"]]
    rows.sort(key=lambda r: r["t_us"])
    grid = [_tuning(r, table_dtype=table_dtype, source="roofline")
            for r in rows[:max_candidates]]
    return grid or [KernelTuning(table_dtype=table_dtype)]


# ---------------------------------------------------------------------------
# the nightly choice-table artifact
# ---------------------------------------------------------------------------

def choice_table(tasks: Optional[Sequence[str]] = None,
                 devices: Sequence[str] = ("cpu", "TPU v5 lite"),
                 batch: int = 4096) -> Dict[str, Any]:
    """Per-(task, device) autotuner choices over the paper configs.

    Pure model output (no training, no timing): layer shapes come from
    the task configs alone, so this runs in seconds and is uploaded by
    the nightly CI as the autotuner audit artifact."""
    from repro.configs import paper_tasks
    from repro.kernels.lut_cascade import vmem_bytes
    tasks = list(tasks or sorted(paper_tasks.TASKS))
    out: Dict[str, Any] = {"batch": batch, "choices": []}
    for task in tasks:
        layers = config_layers(paper_tasks.task_config(task))
        for dev in devices:
            t = pick_tuning(layers, batch=batch, device=dev)
            out["choices"].append({
                "task": task, "device": dev,
                "vmem_bytes": vmem_bytes(layers, mode=t.mode,
                                         block_b=t.block_b,
                                         unit_tile=t.unit_tile),
                "tuning": t.to_meta(),
            })
    return out
