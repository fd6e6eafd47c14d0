"""Fused L-LUT cascade kernels: the ENTIRE folded network in one launch.

The per-layer path (`lut_gather`) pays one kernel dispatch per layer and
re-reads the activations from HBM between layers.  This module executes
every layer of the folded cascade inside a single launch, in one of three
implementations selected by the autotuner (`kernels.autotune`) via
``ops.lut_cascade``:

  * :func:`lut_cascade_xla` — pure-jnp gather cascade.  One fused XLA
    program: per layer, gather the fan-in codes, pack the address with an
    integer weight sum, and gather ``tab[u, addr]`` from a static
    (constant-folded) slice of the bit-packed table buffer.  Bit-exact,
    no Pallas; the fastest path on CPU/GPU where Pallas would run in
    interpret mode.
  * :func:`lut_cascade_pallas` ``mode="resident"`` — single ``pallas_call``,
    grid over batch only, every layer's table VMEM-resident for the whole
    cascade.  Right when the packed buffers fit the VMEM budget (nid and
    the JSC designs; mnist's address matrix alone is ~44 MB).
  * :func:`lut_cascade_pallas` ``mode="streamed"`` — 2-D grid over
    (batch-tile x layer-unit-tile).  Tables and address matrices are cut
    into ``unit_tile``-wide column tiles and streamed HBM->VMEM by the
    Pallas pipeline (the next phase's tiles DMA while the current phase
    runs — automatic double buffering), with the per-phase write offsets
    scalar-prefetched via ``PrefetchScalarGridSpec`` and the activation
    carried across phases in VMEM scratch guarded by ``pl.when``.  Right
    when the packed tables outgrow the VMEM budget.

Shared algebra (docs/KERNELS.md has the full walkthrough):

  * **Tables** for all layers are bit-packed into ONE plan buffer
    ``[total_units, max_entries]`` (int8/int16 when the largest beta
    allows, e.g. 1-bit layers pack 4x denser than int32), each layer a
    static row-slice.
  * **Mapping gathers + address formation** collapse into one MXU matmul
    per layer: with ``A_l[p, u] = sum_f 2^{bits*(F-1-f)} [map_l[u,f] = p]``
    the packed address is ``addr = codes @ A_l`` (assemble layers are the
    contiguous mapping, duplicate fan-in indices just sum their weights).
    All values are integers below 2^24 (planning enforces ``bits*F <=
    24``; paper configs max out at 12), so f32 accumulation is exact, and
    the Pallas modes form the address in :func:`address_passes` one-pass
    bf16 matmuls: ``amat`` split into exact bf16 pieces, the codes into
    8-bit slices (one pass for every paper config).
  * **Lane layout** (Pallas modes): the plan buffers are re-packed on the
    host with every layer's units rounded up to whole 128-lane tiles
    (:func:`lane_layout`), so every lane offset the kernels touch is
    provably aligned, and the tables are transposed to ``[entries,
    units]`` so units sit on lanes.
  * **Lookup** is a select scan over entry rows on the VPU (Pallas
    modes) or a flat gather (XLA mode); padded rows/columns are zero
    everywhere, so full-width padded matmuls stay exact.

Every ``pallas_call`` carries a :func:`cascade_cost_estimate` so XLA's
scheduler sees the kernel's true arithmetic intensity, and a stable
``name`` (``lut_cascade_resident``, ``lut_cascade_streamed``) that a
device trace can find it by.  All three paths are validated bit-exact
against the per-layer 'take' oracle over every paper task config by
tests/test_backends and tests/test_kernels.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing

Array = jax.Array

# static per-layer plan entry (v2): (prev_width, units, entries,
# row_offset, fan_in, in_bits, assemble).  v1 plans carried only the first
# four; the fused backend migrates them before they reach a kernel.
LayerMeta = Tuple[int, int, int, int, int, int, int]


def is_v2_layers(layers: Sequence[Sequence[int]]) -> bool:
    """True when every layer entry carries the v2 ``(fan_in, in_bits,
    assemble)`` tail the XLA path needs."""
    return all(len(l) >= 7 for l in layers)


# ---------------------------------------------------------------------------
# lane layout shared by both Pallas modes
# ---------------------------------------------------------------------------

LANE = 128      # TPU vreg lane width: lane-dim offsets and widths align to it
SUBLANE = 8     # f32/int32 sublane count: the batch tile is a multiple of it


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def lane_layout(layers: Sequence[Sequence[int]], tile: int):
    """Static lane layout of the Pallas kernels' packed buffers.

    Every layer's units are rounded up to a multiple of ``tile`` and laid
    out contiguously, so each layer starts at a ``tile``-aligned column
    and the Mosaic compiler can prove every lane offset aligned.  Returns
    ``(blocks, total, w0p, a_dim)``: per layer ``(in_rows, col, width)``
    (its padded input width, column offset, padded unit width), the total
    padded column count, the input width rounded up to ``LANE``, and the
    activation width (the widest input)."""
    w0p = _round_up(int(layers[0][0]), LANE)
    blocks, col, rows = [], 0, w0p
    for _, units, _, _, *_ in layers:
        width = _round_up(int(units), tile)
        blocks.append((rows, col, width))
        col += width
        rows = width
    a_dim = max(r for r, _, _ in blocks)
    return tuple(blocks), col, w0p, a_dim


# ---------------------------------------------------------------------------
# exact bf16 address passes
# ---------------------------------------------------------------------------

SLICE_BITS = 8  # every integer below 2^8 is exact in bf16 (8 significant bits)


def _v2(layers: Sequence[Sequence[int]]) -> Sequence[Sequence[int]]:
    if not is_v2_layers(layers):
        raise ValueError("lut_cascade_pallas needs v2 layer metadata "
                         "(prev, units, entries, off, fan_in, in_bits, "
                         "assemble); re-plan with the current backend")
    return layers


def code_slices(layers: Sequence[Sequence[int]]) -> int:
    """``n_h``: the 8-bit slices of the widest code any layer reads (the
    input codes and every hidden layer's output: the layers' ``in_bits``).
    One for every paper config."""
    return -(-max(int(l[5]) for l in _v2(layers)) // SLICE_BITS)


def amat_pieces(amat) -> np.ndarray:
    """``[n_a, *amat.shape]`` bf16 pieces whose f32 sum is exactly ``amat``.

    Each piece keeps the top 8 significant bits of what is left (bf16
    truncation), so every piece of a non-negative integer ``amat`` is a
    non-negative integer no larger than its entry, and f32 has 24
    significant bits: three pieces at most.  An ``amat`` of powers of two
    and small sums of them (every paper config) is one piece."""
    amat = np.asarray(amat, np.float32)
    rest, total, pieces = amat, np.zeros_like(amat), []
    for _ in range(3):
        top = rest.view(np.uint32) >> 16        # a bf16's bits: f32's top half
        head = (top << 16).view(np.float32)
        pieces.append(top.astype(np.uint16))
        total += head
        rest = rest - head
        if not rest.any():
            break
    if not np.array_equal(total, amat):
        raise ValueError("amat does not split into exact bf16 pieces")
    return np.stack(pieces).view(jnp.bfloat16)


def max_amat_pieces(layers: Sequence[Sequence[int]]) -> int:
    """The most :func:`amat_pieces` any plan of ``layers`` needs, for the
    cost and VMEM models (which see shapes, not the plan).  An ``amat``
    entry is at most the sum of its layer's fan-in weights, ``b*(F-1)+1``
    bits, and a ``k``-bit integer splits into ``ceil(k/8)`` pieces; an
    assemble layer never repeats a fan-in index, so its entries are single
    powers of two: one piece."""
    return max(1 if asm else -(-(bits * (fan_in - 1) + 1) // SLICE_BITS)
               for _, _, _, _, fan_in, bits, asm in _v2(layers))


def address_passes(amat, layers: Sequence[Sequence[int]]) -> int:
    """One-pass bf16 MXU matmuls per address tile of the Pallas kernels
    for the plan's ``amat`` and v2 ``layers``: ``n_h * n_a``."""
    return code_slices(layers) * len(amat_pieces(amat))


def scan_steps(layers: Sequence[Sequence[int]], *, mode: str = "resident",
               unit_tile: int = LANE) -> int:
    """Select-scan steps per batch tile: every lane chunk scans its
    layer's table entries (resident), or the widest table's (streamed,
    whose phases share one table tile shape).  One step is one
    compare-and-select over a ``[block_b, chunk]`` tile."""
    tile = LANE if mode == "resident" else unit_tile
    blocks, total, _, _ = lane_layout(layers, tile)
    chunk = min(LANE, tile)
    if mode == "streamed":
        return total // chunk * max(int(l[2]) for l in layers)
    return sum(width // chunk * int(l[2])
               for (_, _, width), l in zip(blocks, layers))


def _scratch_dtype(n_h: int):
    """What the activation scratch holds: bf16 codes when one slice covers
    them (exact, and the matmul operand as stored), else int32 to slice."""
    return jnp.bfloat16 if n_h == 1 else jnp.int32


def _as_scratch(codes: Array, dtype) -> Array:
    return codes if codes.dtype == dtype else \
        codes.astype(jnp.float32).astype(dtype)


def pack_lanes(amat, tables, layers: Sequence[Sequence[int]], tile: int):
    """Re-pack the plan's ``amat``/``tables`` into the lane layout.

    Host-side (numpy) and done once per trace: the results are constants
    of the jitted program.  Returns ``amat_k [n_a, a_dim, total]`` bf16
    (the exact pieces of :func:`amat_pieces`) and the transposed tables
    cut into lane chunks, ``tab_k [total // chunk, max_entries, chunk]``
    int32 (``chunk = min(LANE, tile)``; entries on sublanes, units on
    lanes, so a lookup reads one table row per entry at lane offset 0 —
    Mosaic refuses a dynamic row load at any other)."""
    try:
        amat = np.asarray(amat, np.float32)
        tables = np.asarray(tables)
    except jax.errors.TracerArrayConversionError as e:
        raise TypeError("lut_cascade_pallas needs concrete amat/tables "
                        "(plan constants), not traced values") from e
    blocks, total, _, a_dim = lane_layout(layers, tile)
    amat_k = np.zeros((a_dim, total), np.float32)
    tab_k = np.zeros((tables.shape[1], total), np.int32)
    for (prev, units, _, off, *_), (_, col, _) in zip(layers, blocks):
        amat_k[:prev, col:col + units] = amat[:prev, off:off + units]
        tab_k[:, col:col + units] = tables[off:off + units].T
    chunk = min(LANE, tile)
    tab_k = tab_k.reshape(len(tab_k), total // chunk, chunk)
    return amat_pieces(amat_k), np.ascontiguousarray(tab_k.transpose(1, 0, 2))


def _lookup(tab_ref, addr: Array, n_entries: int) -> Array:
    """``out[b, u] = tab[addr[b, u], u]`` for one ``[entries, chunk]``
    table tile.

    A select scan over the table's entry rows: every step compares the
    whole ``[block_b, chunk]`` address tile with one entry index and
    keeps that entry's table row where it matches.  Units stay on lanes
    throughout, so nothing is padded and the carry fits in vregs."""
    def body(e, acc):
        row = tab_ref[pl.ds(e, 1), :]                    # [1, chunk] int32
        return jnp.where(addr == e, row, acc)
    return jax.lax.fori_loop(0, n_entries, body,
                             jnp.zeros(addr.shape, jnp.int32))


def _address(h: Array, amat_ref, cols, n_h: int) -> Array:
    """Gather + address packing for the ``amat_ref`` columns ``cols``:
    ``addr = sum_{i,j} 2^{8i} (h_i @ a_j)`` over the 8-bit slices ``h_i``
    of the codes and the bf16 pieces ``a_j`` of ``amat``, each a one-pass
    bf16 matmul.  Every operand is an integer exact in bf16 and every
    partial sum a non-negative integer below the address (< 2^24), so the
    f32 accumulation is exact."""
    rows = h.shape[1]
    addr = None
    for i in range(n_h):
        hi = h if n_h == 1 else _as_scratch(
            (h >> (SLICE_BITS * i)) & (2 ** SLICE_BITS - 1), jnp.bfloat16)
        for j in range(amat_ref.shape[0]):
            part = jnp.dot(hi, amat_ref[j, 0:rows, cols],
                           preferred_element_type=jnp.float32)
            if i:
                part = part * float(2 ** (SLICE_BITS * i))
            addr = part if addr is None else addr + part
    return addr.astype(jnp.int32)


# ---------------------------------------------------------------------------
# cost and VMEM model shared by both Pallas modes
# ---------------------------------------------------------------------------

def cascade_flops(layers: Sequence[Sequence[int]], batch: int, *,
                  n_a: Optional[int] = None) -> int:
    """Work of one cascade pass: per layer, the address formation's
    one-pass bf16 matmuls (``n_h * n_a`` passes of 2*B*prev*units MXU
    flops) plus the lookup's select scan (2*B*units*entries compare/select
    VPU ops).  ``n_a`` is the plan's piece count, when known; otherwise
    the most these layers can need (:func:`max_amat_pieces`)."""
    passes = code_slices(layers) * (n_a or max_amat_pieces(layers))
    f = 0
    for prev, units, entries, _, *_ in layers:
        f += 2 * batch * prev * units * passes + 2 * batch * units * entries
    return f


def cascade_bytes(layers: Sequence[Sequence[int]], batch: int,
                  *, mode: str = "resident", block_b: int = 256,
                  unit_tile: int = LANE, n_a: Optional[int] = None) -> int:
    """HBM bytes of one cascade pass over the lane-packed constants: the
    bf16 ``amat`` pieces, the int32 tables, the int32 codes in and out.

    Resident mode reads the packed buffers once; streamed mode re-streams
    the table/amat tiles for every batch tile (that re-read is the price
    of never holding the full table set in VMEM)."""
    tile = LANE if mode == "resident" else unit_tile
    blocks, total, w0p, a_dim = lane_layout(layers, tile)
    max_entries = max(int(e) for _, _, e, _, *_ in layers)
    n_a = n_a or max_amat_pieces(layers)
    const = (n_a * a_dim * 2 + max_entries * 4) * total
    io = batch * (w0p + blocks[-1][2]) * 4
    if mode == "streamed":
        n_bt = max(1, math.ceil(batch / block_b))
        return io + n_bt * const
    return io + const


def cascade_cost_estimate(layers: Sequence[Sequence[int]], batch: int,
                          *, mode: str = "resident", block_b: int = 256,
                          unit_tile: int = LANE,
                          n_a: Optional[int] = None) -> pl.CostEstimate:
    """``pl.CostEstimate`` for one fused-cascade launch (both modes)."""
    return pl.CostEstimate(
        flops=cascade_flops(layers, batch, n_a=n_a),
        bytes_accessed=cascade_bytes(layers, batch, mode=mode,
                                     block_b=block_b, unit_tile=unit_tile,
                                     n_a=n_a),
        transcendentals=0)


def vmem_bytes(layers: Sequence[Sequence[int]], *, mode: str,
               block_b: int, unit_tile: int = LANE) -> int:
    """Upper bound on the VMEM the compiled kernel allocates, lane and
    sublane padding in.

    Counts every blocked operand double-buffered (constant index maps
    included): the int32 codes in and out, the bf16 ``amat`` pieces (as
    many as :func:`max_amat_pieces` allows), the int32 tables; both
    activation scratches (bf16 codes, or int32 where codes need more
    than one 8-bit slice), and the working values of one unit chunk —
    the loaded ``[block_b, a_dim]`` activation (with the int32 slice and
    its f32 and bf16 copies when sliced) and the address/carry tiles.
    Checked against the v5e compiler at 128-lane tiles
    (tests/test_tpu_compile.py); wider streamed tiles allocate more than
    this counts."""
    tile = LANE if mode == "resident" else unit_tile
    blocks, total, w0p, a_dim = lane_layout(layers, tile)
    ents = _round_up(max(int(e) for _, _, e, _, *_ in layers), SUBLANE)
    n_a = max_amat_pieces(layers)
    sliced = code_slices(layers) > 1
    h_bytes = 4 if sliced else 2
    h_rows = _round_up(block_b, SUBLANE * 4 // h_bytes)   # bf16 packs 16
    chunk = min(LANE, tile)
    cols = total if mode == "resident" else unit_tile
    io = 2 * block_b * (w0p + blocks[-1][2]) * 4
    consts = 2 * (n_a * a_dim * 2 + ents * 4) * cols
    scratch = 2 * h_rows * a_dim * h_bytes
    work = (h_rows * a_dim * (h_bytes + (4 + 4 + 2 if sliced else 0))
            + 4 * block_b * chunk * 4)
    return io + consts + scratch + work


# ---------------------------------------------------------------------------
# mode "resident": grid over batch, all tables VMEM-resident
# ---------------------------------------------------------------------------

def _resident_kernel(codes_ref, amat_ref, tab_ref, out_ref, h0_ref, h1_ref,
                     *, blocks, entries: Tuple[int, ...], chunk: int,
                     n_h: int):
    """One batch tile through every layer; tables stay resident.

    Layer ``l`` reads its input from scratch ``h[(l-1) % 2]`` (the codes
    for layer 0) and writes its output codes chunk by chunk into
    ``h[l % 2]`` (the output block for the last layer)."""
    bufs = (h0_ref, h1_ref)
    last = len(blocks) - 1
    for l, ((rows, col, width), n_e) in enumerate(zip(blocks, entries)):
        if l == 0:
            h = _as_scratch(codes_ref[...], h0_ref.dtype)   # [BB, rows]
        else:
            h = bufs[(l - 1) % 2][:, 0:rows]
        for c0 in range(0, width, chunk):
            cols = slice(col + c0, col + c0 + chunk)
            addr = _address(h, amat_ref, cols, n_h)
            codes = _lookup(tab_ref.at[(col + c0) // chunk], addr, n_e)
            if l == last:
                out_ref[:, c0:c0 + chunk] = codes
            else:
                bufs[l % 2][:, c0:c0 + chunk] = _as_scratch(
                    codes, h0_ref.dtype)


@functools.partial(jax.jit, static_argnames=("layers", "block_b",
                                             "interpret"))
def _resident_call(codes_p: Array, amat_k: Array, tab_k: Array, *,
                   layers: Tuple[LayerMeta, ...], block_b: int,
                   interpret: bool) -> Array:
    bb, w0p = codes_p.shape
    blocks, total, _, a_dim = lane_layout(layers, LANE)
    n_out_p = blocks[-1][2]
    n_h = code_slices(layers)
    h_dtype = _scratch_dtype(n_h)
    return pl.pallas_call(
        functools.partial(_resident_kernel, blocks=blocks,
                          entries=tuple(e for _, _, e, *_ in layers),
                          chunk=LANE, n_h=n_h),
        grid=(bb // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, w0p), lambda i: (i, 0)),
            pl.BlockSpec(amat_k.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(tab_k.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_out_p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, n_out_p), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b, a_dim), h_dtype),
                        pltpu.VMEM((block_b, a_dim), h_dtype)],
        cost_estimate=cascade_cost_estimate(layers, bb, mode="resident",
                                            block_b=block_b,
                                            n_a=amat_k.shape[0]),
        interpret=interpret,
        name="lut_cascade_resident",
    )(codes_p, amat_k, tab_k)


# ---------------------------------------------------------------------------
# mode "streamed": 2-D grid (batch-tile x phase), tiles streamed HBM->VMEM
# ---------------------------------------------------------------------------

def _phase_layout(layers: Tuple[LayerMeta, ...], unit_tile: int):
    """Static phase plan for the streamed kernel.

    A *phase* is one ``unit_tile``-wide column block of the lane layout,
    i.e. one (layer, unit-tile) pair; phases run sequentially on the inner
    grid axis, and phase ``j`` streams column block ``j`` of the packed
    buffers.  Returns the per-phase scalar-prefetch arrays: the column
    offset within its layer, and the layer-end and output flags."""
    cols, ends, outs = [], [], []
    blocks, _, _, _ = lane_layout(layers, unit_tile)
    last = len(blocks) - 1
    for li, (_, _, width) in enumerate(blocks):
        n_t = width // unit_tile
        for c in range(n_t):
            cols.append(c * unit_tile)
            ends.append(1 if c == n_t - 1 else 0)
            outs.append(1 if li == last else 0)
    return (np.asarray(cols, np.int32), np.asarray(ends, np.int32),
            np.asarray(outs, np.int32))


def _streamed_kernel(col_ref, end_ref, emit_ref,            # scalar prefetch
                     codes_ref, amat_ref, tab_ref, out_ref,
                     h_ref, hn_ref, *, w0p: int, unit_tile: int,
                     chunk: int, n_entries: int, n_h: int):
    """One (batch-tile, phase) grid step.

    ``h_ref`` holds the current layer's *input* codes (zero-padded to
    ``a_dim``; :func:`_scratch_dtype`); ``hn_ref`` collects the layer's
    output tile by tile.  Both live in VMEM scratch and persist across the
    sequential phase axis.  ``amat_ref``/``tab_ref`` see only this phase's
    tile — the Pallas pipeline fetches phase j+1's tiles while phase j
    computes."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _load_input():                        # first phase of the cascade
        h_ref[...] = jnp.zeros(h_ref.shape, h_ref.dtype)
        hn_ref[...] = jnp.zeros(hn_ref.shape, hn_ref.dtype)
        h_ref[:, 0:w0p] = _as_scratch(codes_ref[...], h_ref.dtype)

    # address formation over the FULL padded width: padded h columns and
    # padded amat rows are both zero, so the wide matmul is exact.
    h = h_ref[...]
    col = col_ref[j]
    emit = emit_ref[j] == 1
    for c0 in range(0, unit_tile, chunk):
        addr = _address(h, amat_ref, slice(c0, c0 + chunk), n_h)
        codes = _lookup(tab_ref.at[c0 // chunk], addr, n_entries)
        at = pl.ds(pl.multiple_of(col + c0, chunk), chunk)
        hn_ref[:, at] = _as_scratch(codes, hn_ref.dtype)

        @pl.when(emit)
        def _emit():                          # final layer: write codes out
            out_ref[:, at] = codes

    @pl.when(end_ref[j] == 1)
    def _layer_end():                         # output becomes next input
        h_ref[...] = hn_ref[...]


@functools.partial(jax.jit, static_argnames=("layers", "block_b",
                                             "unit_tile", "interpret"))
def _streamed_call(codes_p: Array, amat_k: Array, tab_k: Array, *,
                   layers: Tuple[LayerMeta, ...], block_b: int,
                   unit_tile: int, interpret: bool) -> Array:
    bb, w0p = codes_p.shape
    blocks, _, _, a_dim = lane_layout(layers, unit_tile)
    cols, ends, outs = _phase_layout(layers, unit_tile)
    n_entries = tab_k.shape[1]
    chunk = min(LANE, unit_tile)
    n_out_p = blocks[-1][2]
    n_a = amat_k.shape[0]
    n_h = code_slices(layers)
    h_dtype = _scratch_dtype(n_h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bb // block_b, len(cols)),
        in_specs=[
            pl.BlockSpec((block_b, w0p), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((n_a, a_dim, unit_tile),
                         lambda i, j, *_: (0, 0, j)),
            pl.BlockSpec((unit_tile // chunk, n_entries, chunk),
                         lambda i, j, *_: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_out_p), lambda i, j, *_: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_b, a_dim), h_dtype),   # h (layer input)
            pltpu.VMEM((block_b, a_dim), h_dtype),   # h_next (output)
        ],
    )
    return pl.pallas_call(
        functools.partial(_streamed_kernel, w0p=w0p, unit_tile=unit_tile,
                          chunk=chunk, n_entries=n_entries, n_h=n_h),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bb, n_out_p), jnp.int32),
        cost_estimate=cascade_cost_estimate(
            layers, bb, mode="streamed", block_b=block_b,
            unit_tile=unit_tile, n_a=n_a),
        interpret=interpret,
        name="lut_cascade_streamed",
    )(jnp.asarray(cols), jnp.asarray(ends), jnp.asarray(outs),
      codes_p, amat_k, tab_k)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def lut_cascade_pallas(codes: Array, amat, tables, *,
                       layers: Tuple[LayerMeta, ...], block_b: int = 256,
                       mode: str = "resident", unit_tile: int = LANE,
                       interpret: bool = True) -> Array:
    """Run the whole folded cascade in a single ``pallas_call``.

    codes:  [batch, in_features] int32 input codes.
    amat:   [max_prev, total_units] f32 — per-layer address-formation
            matrices packed block-wise (layer l occupies rows [0:prev_l],
            cols [off_l : off_l+units_l]).  Concrete (a plan constant).
    tables: [total_units, max_entries] int — per-layer tables packed along
            rows at the same offsets (narrow dtype allowed).  Concrete.
    layers: static per-layer v2 metadata, ``(prev, units, entries, off,
            fan_in, in_bits, assemble)``; ``in_bits`` sets how many 8-bit
            slices the address matmul takes of the codes.
    mode:   "resident" (1-D batch grid, tables VMEM-resident) or
            "streamed" (2-D batch x phase grid, tiles streamed HBM->VMEM
            with scalar-prefetched offsets).  ``unit_tile`` sets the
            streamed tile width (a multiple of ``LANE`` when compiled);
            the autotuner picks both.
    """
    if mode not in ("resident", "streamed"):
        raise ValueError(f"unknown lut_cascade mode {mode!r}")
    if not interpret and (mode == "streamed" and unit_tile % LANE
                          or block_b % SUBLANE):
        raise ValueError(
            f"compiled lut_cascade needs unit_tile % {LANE} == 0 and "
            f"block_b % {SUBLANE} == 0, got {unit_tile}, {block_b}")
    layers = tuple(tuple(int(v) for v in l) for l in _v2(layers))
    batch = codes.shape[0]
    # never tile wider than the batch itself (rounded up to a power of two,
    # floored at the sublane count): under batch-sharded placement each
    # device sees batch/n rows, and padding those to a full tile would
    # waste most of the kernel's work
    block_b = min(block_b, max(SUBLANE, 1 << (batch - 1).bit_length()))
    tile = LANE if mode == "resident" else unit_tile
    amat_k, tab_k = pack_lanes(amat, tables, layers, tile)
    tracing.count("lut_cascade.address_passes",
                  code_slices(layers) * len(amat_k))
    tracing.count("lut_cascade.scan_steps",
                  scan_steps(layers, mode=mode, unit_tile=unit_tile))
    _, _, w0p, _ = lane_layout(layers, tile)
    # zero rows and columns: valid addresses, multiplied by zero amat rows
    codes_p = jnp.pad(codes.astype(jnp.int32),
                      ((0, (-batch) % block_b), (0, w0p - codes.shape[1])))
    if mode == "streamed":
        out = _streamed_call(codes_p, amat_k, tab_k, layers=layers,
                             block_b=block_b, unit_tile=unit_tile,
                             interpret=interpret)
    else:
        out = _resident_call(codes_p, amat_k, tab_k, layers=layers,
                             block_b=block_b, interpret=interpret)
    return out[:batch, :layers[-1][1]]


@functools.partial(jax.jit, static_argnames=("layers",))
def lut_cascade_xla(codes: Array, tables: Array,
                    mappings: Tuple[Optional[Array], ...], *,
                    layers: Tuple[Tuple[int, ...], ...]) -> Array:
    """Pure-jnp fused cascade: per-layer gathers on the packed table buffer.

    The whole cascade lowers to ONE XLA program with, per layer, a gather
    of the fan-in codes, an integer weight-sum address pack, and a
    row-indexed table gather ``tab[u, addr[b, u]]`` — no one-hot
    materialization, so it is the fastest fused path wherever Pallas would
    run interpreted (CPU/GPU).  Bit-exact vs the Pallas modes and the
    per-layer oracle.

    Each layer's table is a *static* slice ``tables[off:off+units,
    :entries]`` of the packed buffer, which XLA constant-folds, so the hot
    program is op-for-op the per-layer oracle's gather (a flat 1-D
    ``jnp.take`` over the whole packed buffer measures ~10% slower on CPU:
    its clip-mode clamp and base-offset add survive into the optimized
    HLO as extra compare/select/broadcast chains per layer).

    codes:    [batch, in_features] int32.
    tables:   [total_units, max_entries] packed tables (narrow dtype ok).
    mappings: per layer, the [units, fan_in] int32 mapping — or ``None``
              for assemble layers (their mapping is the identity reshape).
    layers:   static v2 7-tuples
              ``(prev, units, entries, off, fan_in, in_bits, assemble)``.
    """
    if not is_v2_layers(layers):
        raise ValueError("lut_cascade_xla needs v2 layer metadata "
                         "(prev, units, entries, off, fan_in, in_bits, "
                         "assemble); re-plan with the current backend")
    h = codes.astype(jnp.int32)
    for (prev, units, entries, off, fan_in, bits, asm), mp in zip(
            layers, mappings):
        if asm:
            ci = h.reshape(h.shape[0], units, fan_in)
        else:
            ci = h[:, mp]                                # [B, U, F]
        w = jnp.asarray(2 ** (bits * np.arange(fan_in - 1, -1, -1)),
                        jnp.int32)
        addr = jnp.sum(ci * w, axis=-1, dtype=jnp.int32)  # [B, U]
        tab = tables[off:off + units, :entries].astype(jnp.int32)
        h = jax.vmap(lambda a, t=tab: t[jnp.arange(t.shape[0]), a])(addr)
    return h
