"""Pallas strider kernel: on-device database-page decode (TPU target).

The TPU incarnation of the paper's access engine. One grid step = a group of
``PAGES_PER_STEP`` pages: the BlockSpec streams their 32 KB pages from HBM
into VMEM (the analogue of a BRAM page buffer), the kernel parses the dynamic
header fields, extracts the tuple payloads at the compiler-derived static
stride, converts to float32 (dequantizing int8 payloads), and writes dense
tiles for the execution engine — data never bounces through the host.

Static geometry (slot stride, payload width, region offset) comes from the
same compiled Strider program the ISA interpreter runs; per-page dynamic state
(n_tuples) is read from the page header in-kernel, mirroring the ISA's
readB/extrB header-processing phase.

TPU layout. The page group is a ``(G, page_words)`` tile and each tuple slot
is a lane window of it, read for all G pages at once — the TPU compiler
cannot split a page's word vector into a ``(tuples, stride)`` matrix. So the
kernel loops over the slots: it loads the 128-lane-aligned window around the
slot's tuple, rotates the tuple to lane 0, and writes slot-major tiles
``(T, G, D + 2)``: the decoded columns, then the label, then the live mask.
``strider_decode`` transposes them back to page-major order outside the
kernel. Int8 payloads are split into four byte planes and interleaved back
into column order by a one-hot MXU matmul (bytes 0..255 are exact in every
matmul precision).

VMEM per grid step (double-buffered): 2 x (G pages + the (T, G, D + 2) f32
tile); ``ops.check_vmem`` checks it before launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.striders import ProjectionPlan
from repro.db.page import TUPLE_HEADER_BYTES, PageLayout

PAGES_PER_STEP = 8  # G: one sublane tile of pages per grid step
LANES = 128


def _word_runs(words: tuple[int, ...]) -> list[tuple[int, int]]:
    """Merge sorted word indices into contiguous [start, stop) runs — each
    becomes one static VMEM slice, the kernel analogue of the projected
    Strider program's per-run ``writeB``."""
    runs: list[tuple[int, int]] = []
    for w in words:
        if runs and runs[-1][1] == w:
            runs[-1] = (runs[-1][0], w + 1)
        else:
            runs.append((w, w + 1))
    return runs


def _geometry(layout: PageLayout, plan: ProjectionPlan | None):
    """(payload word runs, byte runs | None, include_label, n_columns): what
    the kernel reads from each tuple. Byte runs pick columns out of the
    interleaved bytes of the read words (int8 layouts only)."""
    if plan is None:
        n_words = layout.payload_bytes // 4
        byte_runs = [(0, layout.n_features)] if layout.quantized else None
        return [(0, n_words)], byte_runs, True, layout.n_features
    byte_runs = (_word_runs(tuple(plan.column_byte_positions()))
                 if layout.quantized else None)
    return _word_runs(plan.words), byte_runs, plan.include_label, plan.n_columns


def _lanes(x, runs):
    """Concatenate static lane windows [a, b) of ``x``."""
    parts = [x[:, a:b] for a, b in runs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _interleave_bytes(words):
    """(G, W) uint32 -> (G, 4W) f32 byte values in memory order (byte k of
    word w at column 4w + k): four byte planes, then a one-hot matmul per
    window of up to 128 words puts each byte in its column."""
    planes = [
        ((words >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)).astype(jnp.int32)
        .astype(jnp.float32)
        for k in range(4)
    ]
    out = []
    n_words = words.shape[1]
    for w0 in range(0, n_words, LANES):
        cw = min(LANES, n_words - w0)
        src = jnp.concatenate([p[:, w0:w0 + cw] for p in planes], axis=1)
        r = jax.lax.broadcasted_iota(jnp.int32, (4 * cw, 4 * cw), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (4 * cw, 4 * cw), 1)
        onehot = ((r % cw) * 4 + r // cw == c).astype(jnp.float32)
        out.append(jax.lax.dot_general(
            src, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _strider_kernel(page_ref, out_ref, *, layout: PageLayout,
                    plan: ProjectionPlan | None):
    t = layout.tuples_per_page
    pw = layout.page_words
    stride_w = layout.stride // 4
    hdr_w = TUPLE_HEADER_BYTES // 4
    payload_w = layout.payload_bytes // 4
    region_start_w = (layout.data_end - t * layout.stride) // 4
    word_runs, byte_runs, include_label, _ = _geometry(layout, plan)
    g = page_ref.shape[0]
    # words of a tuple the kernel reads (payload runs, then the label) and the
    # 128-aligned window that holds them wherever the tuple starts
    span = payload_w + 1 if include_label else max([b for _, b in word_runs])
    win = min(-(-(span + LANES - 1) // LANES) * LANES, pw)

    # --- page header processing (dynamic per-page state) --------------------
    n_tuples = jax.lax.bitcast_convert_type(page_ref[:, 4:5], jnp.int32)
    if layout.quantized:
        sw = layout.data_end // 4
        scale = jax.lax.bitcast_convert_type(page_ref[:, sw:sw + 1], jnp.float32)

    # --- affine tuple extraction (static geometry from the Strider program):
    # slot i's payload starts at region_start + (T-1-i) * stride + header
    # (downward packing). Lane windows must start 128-aligned, so each slot
    # loads the aligned window around its tuple and rotates the tuple to
    # lane 0 -------------------------------------------------------------------
    def slot(i, carry):
        off = region_start_w + (t - 1 - i) * stride_w + hdr_w
        start = pl.multiple_of(jnp.minimum(off // LANES * LANES, pw - win),
                               LANES)
        tup = pltpu.roll(page_ref[:, pl.ds(start, win)],
                         (win - (off - start)) % win, 1)
        if not word_runs:  # label-only projection
            feats = jnp.zeros((g, 0), jnp.float32)
        elif layout.quantized:
            raw = _lanes(_interleave_bytes(_lanes(tup, word_runs)), byte_runs)
            feats = (raw - 128.0) * scale
        else:
            feats = jax.lax.bitcast_convert_type(_lanes(tup, word_runs),
                                                 jnp.float32)
        if include_label:
            lab = jax.lax.bitcast_convert_type(
                tup[:, payload_w:payload_w + 1], jnp.float32
            )
        else:
            lab = jnp.zeros((g, 1), jnp.float32)
        row = jnp.concatenate([feats, lab, jnp.ones((g, 1), jnp.float32)],
                              axis=1)
        # cleanse: zero dead slots (partial last page). Select, not multiply:
        # payload words may be arbitrary bit patterns (int32 tokens stored as
        # f32 denormals) that float arithmetic would flush or NaN-propagate
        out_ref[i] = jnp.where(n_tuples > i, row, 0.0)
        return carry

    jax.lax.fori_loop(0, t, slot, 0)


def strider_decode(
    pages: jnp.ndarray, layout: PageLayout, interpret: bool = False,
    plan: ProjectionPlan | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """pages (P, page_words) uint32 -> (feats (P,T,D), labels (P,T), mask (P,T)).

    With a ``plan``, D is ``plan.n_columns`` and the kernel only touches the
    projected payload words (pushdown)."""
    p = pages.shape[0]
    t = layout.tuples_per_page
    d = _geometry(layout, plan)[3]
    pw = layout.page_words
    g = PAGES_PER_STEP
    p_pad = -(-p // g) * g
    if p_pad != p:  # zero pages hold no tuples: their slots decode as dead
        pages = jnp.pad(pages, ((0, p_pad - p), (0, 0)))

    kernel = functools.partial(_strider_kernel, layout=layout, plan=plan)
    out = pl.pallas_call(
        kernel,
        grid=(p_pad // g,),
        in_specs=[pl.BlockSpec((g, pw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((t, g, d + 2), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, p_pad, d + 2), jnp.float32),
        interpret=interpret,
        name="strider_decode",
    )(pages)
    out = jnp.transpose(out[:, :p], (1, 0, 2))  # slot-major -> page-major
    return out[..., :d], out[..., d], out[..., d + 1]
