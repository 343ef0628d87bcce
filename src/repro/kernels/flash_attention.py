"""Blocked (flash-style) causal attention Pallas TPU kernel.

Online-softmax attention with GQA, optional sliding window and logit
softcap — one kernel covers phi3/llama (full causal), gemma2/3 (window +
softcap) and the hybrid's shared block.  VMEM working set per grid step:
one (bq, hd) query tile, one (bk, hd) K/V tile pair and the f32
running (m, l, acc) scratch; K/V tiles stream down the innermost grid
dimension, and out-of-band blocks (beyond causal front or behind the
sliding window) are skipped via ``pl.when`` so windowed layers do
O(S·W) work, not O(S²).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, softcap: float, window: int, bq: int, bk: int,
            n_k: int):
    i = pl.program_id(2)          # query block
    j = pl.program_id(3)          # kv block (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # band check: is this (i, j) block inside the causal/window band?
    q_lo, q_hi = i * bq, i * bq + bq - 1
    k_lo, k_hi = j * bk, j * bk + bk - 1
    relevant = k_lo <= q_hi
    if window > 0:
        relevant &= k_hi > q_lo - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, 0]                     # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = (l_scr[...][:, 0] * alpha + jnp.sum(p, axis=1))[:, None]
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]

    @pl.when(j == n_k - 1)
    def _finalize():
        l = l_scr[...][:, 0]
        o_ref[0, 0, ...] = (acc_scr[...]
                            / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _online_update(q, k, v, mask, m_scr, l_scr, acc_scr, g: int, *,
                   scale: float, softcap: float):
    """One online-softmax step of GQA group ``g``: rows ``q`` (R, hd)
    against keys/values ``k``/``v`` (T, hd) under ``mask`` (R, T).  The
    group's running (m, l, acc) live at index ``g`` of the scratch."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[g]                                 # (R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[g] = acc_scr[g] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[g] = m_new


def _page_update(q_ref, k_ref, v_ref, ks_ref, vs_ref, mask, m_scr, l_scr,
                 acc_scr, *, scale: float, softcap: float):
    """Attend every GQA group to the page resident in ``k_ref``/``v_ref``
    (1, bs, K, hd): kv-head ``g`` serves query rows ``q_ref[0, g]``.  An
    int8 page is dequantized here, against its (1, bs, K) scale tiles,
    right before the dot."""
    for g in range(k_ref.shape[2]):
        k = k_ref[0, :, g, :].astype(jnp.float32)     # (bs, hd)
        v = v_ref[0, :, g, :].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0, :, g:g + 1]
            v = v * vs_ref[0, :, g:g + 1]
        _online_update(q_ref[0, g].astype(jnp.float32), k, v, mask,
                       m_scr, l_scr, acc_scr, g, scale=scale, softcap=softcap)


def _paged_kernel(*refs, scale: float, softcap: float,
                  bs: int, n_blk: int, quant: bool):
    """Paged-attention decode read: one query token per sequence against
    KV pages selected by the scalar-prefetched block table.

    Grid (B, n_blk); the inner dimension walks the LOGICAL blocks of one
    sequence while the BlockSpec index_map streams in the whole PHYSICAL
    page ``block_tables[b, j]`` (all K kv-heads), so each page is read
    once per sequence and serves all H query heads, group by group.  The
    gather never materialises; unallocated (-1) entries are clipped to
    page 0 by the index_map and skipped here.

    ``quant=True`` adds per-(page, offset, kv-head) f32 scale tiles
    streamed through the same index_map as the int8 K/V pages; the
    dequant multiply happens in VREGs right before the dot, so the f32
    pool never exists anywhere.
    """
    if quant:
        (bt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (bt_ref, len_ref, q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(bt_ref[b, j] >= 0)
    def _compute():
        t = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], bs), 1)
        _page_update(q_ref, k_ref, v_ref, ks_ref, vs_ref, t < len_ref[b],
                     m_scr, l_scr, acc_scr, scale=scale, softcap=softcap)

    @pl.when(j == n_blk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def _paged_specs(Kh: int, rows: int, hd: int, bs: int, n_blk: int,
                 quant: bool):
    """BlockSpecs shared by the paged decode and extend kernels over a
    (b, j) grid: the row's (1, K, rows, hd) query tile, whole pages
    (1, bs, K, hd) looked up through the block table (``j`` past the
    table is clamped to its last entry), the (1, bs, K) scale tiles of
    an int8 pool, and (K, rows, ·) online-softmax scratch.  Every block
    spans whole trailing dimensions, which is what the TPU's (8, 128)
    tiling rule asks of a block narrower than the tile."""
    def phys(bt_r, b, j):
        return jnp.maximum(bt_r[b, jnp.minimum(j, n_blk - 1)], 0)

    row = pl.BlockSpec((1, Kh, rows, hd), lambda b, j, bt_r, s_r: (b, 0, 0, 0))
    page = pl.BlockSpec((1, bs, Kh, hd),
                        lambda b, j, bt_r, s_r: (phys(bt_r, b, j), 0, 0, 0))
    pages = [page, page]
    if quant:
        sc = pl.BlockSpec((1, bs, Kh),
                          lambda b, j, bt_r, s_r: (phys(bt_r, b, j), 0, 0))
        pages += [sc, sc]
    scratch = [pltpu.VMEM((Kh, rows, 1), jnp.float32),
               pltpu.VMEM((Kh, rows, 1), jnp.float32),
               pltpu.VMEM((Kh, rows, hd), jnp.float32)]
    return row, pages, scratch


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale: float, softcap: float = 0.0,
                    k_scale=None, v_scale=None,
                    interpret: bool = False):
    """Paged single-token decode attention (GQA).

    q: (B, H, hd); k_pages/v_pages: (num_blocks, bs, K, hd) shared page
    pool; block_tables: (B, n_blk) int32 physical page per logical block
    (-1 = unallocated); lengths: (B,) valid context per row.  The block
    table and lengths ride the scalar-prefetch channel so the page
    lookup happens in the BlockSpec index_map (the vLLM-on-TPU layout).

    For an int8 pool pass ``k_scale``/``v_scale`` (num_blocks, bs, K):
    the scale tiles stream through the same page index_map and the
    dequant fuses into the attention read.  Returns (B, H, hd).
    """
    B, H, hd = q.shape
    nB, bs, Kh, _ = k_pages.shape
    n_blk = block_tables.shape[1]
    G = H // Kh
    quant = k_scale is not None
    row, pages, scratch = _paged_specs(Kh, G, hd, bs, n_blk, quant)
    operands = [q.reshape(B, Kh, G, hd), k_pages, v_pages]
    if quant:
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blk),
        in_specs=[row] + pages,
        out_specs=row,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, softcap=softcap,
                          bs=bs, n_blk=n_blk, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, hd), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(B, H, hd)


def _paged_extend_kernel(*refs, scale: float, softcap: float,
                         bs: int, n_blk: int, s_len: int, quant: bool):
    """Fused multi-token extend read: S queries per row walk the row's
    context pages (masked strictly below ``pos`` — the pre-write view),
    then attend the S-token suffix causally at grid step ``j == n_blk``
    and finalize there.

    Query rows of group ``g`` are laid out ``(G, S)``-major, so row
    ``r`` sits at suffix position ``r % S``.  The suffix K/V arrives as
    a dense (B, S, K, hd) operand — on a quantized pool the caller
    passes the int8 ROUND-TRIP so the scored logits match what later
    page reads reconstruct.
    """
    if quant:
        (bt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
         kn_ref, vn_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (bt_ref, pos_ref, q_ref, k_ref, v_ref,
         kn_ref, vn_ref, o_ref, m_scr, l_scr, acc_scr) = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)
    rows = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((j < n_blk) & (bt_ref[b, jnp.minimum(j, n_blk - 1)] >= 0))
    def _context():
        t = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        _page_update(q_ref, k_ref, v_ref, ks_ref, vs_ref, t < pos_ref[b],
                     m_scr, l_scr, acc_scr, scale=scale, softcap=softcap)

    @pl.when(j == n_blk)
    def _suffix():
        qi = jax.lax.broadcasted_iota(jnp.int32, (rows, s_len), 0) % s_len
        kj = jax.lax.broadcasted_iota(jnp.int32, (rows, s_len), 1)
        _page_update(q_ref, kn_ref, vn_ref, None, None, kj <= qi,
                     m_scr, l_scr, acc_scr, scale=scale, softcap=softcap)
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def paged_extend_attention(q, k_pages, v_pages, k_new, v_new,
                           block_tables, pos, *, scale: float,
                           softcap: float = 0.0,
                           k_scale=None, v_scale=None,
                           interpret: bool = False):
    """Paged multi-token extend attention (GQA) — the fused twin of the
    gather read in ``models.layers.attention_extend_paged``.

    q: (B, S, H, hd) new-token queries at absolute positions
    ``pos + i``; k_new/v_new: (B, S, K, hd) the suffix K/V they attend
    causally; k_pages/v_pages: (num_blocks, bs, K, hd) pool (context is
    the PRE-write view, masked strictly below ``pos``); block_tables:
    (B, n_blk) int32 (-1 = unallocated); pos: (B,) int32.  Optional
    ``k_scale``/``v_scale`` (num_blocks, bs, K) fuse the int8 dequant
    into the page read.  Returns (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    nB, bs, Kh, _ = k_pages.shape
    n_blk = block_tables.shape[1]
    G = H // Kh
    quant = k_scale is not None
    row, pages, scratch = _paged_specs(Kh, G * S, hd, bs, n_blk, quant)
    suffix = pl.BlockSpec((1, S, Kh, hd),
                          lambda b, j, bt_r, ps_r: (b, 0, 0, 0))
    qg = q.reshape(B, S, Kh, G, hd).transpose(0, 2, 3, 1, 4)
    operands = [qg.reshape(B, Kh, G * S, hd), k_pages, v_pages]
    if quant:
        operands += [k_scale, v_scale]
    operands += [k_new, v_new]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blk + 1),
        in_specs=[row] + pages + [suffix, suffix],
        out_specs=row,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_paged_extend_kernel, scale=scale,
                          softcap=softcap, bs=bs, n_blk=n_blk, s_len=S,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kh, G * S, hd), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32),
      jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)), *operands)
    out = out.reshape(B, Kh, G, S, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S, H, hd)


def _pick_block(n: int, pref=(512, 256, 128, 64, 32, 16, 8)) -> int:
    for c in pref:
        if n % c == 0 and c <= n:
            return c
    return n


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "softcap", "interpret"))
def flash_attention(q, k, v, *, scale: float, window: int = 0,
                    softcap: float = 0.0, interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, T, K, hd) with H % K == 0 (GQA).

    Causal; ``window`` > 0 adds a sliding window.  Returns (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    _, T, Kh, _ = k.shape
    G = H // Kh
    qt = q.transpose(0, 2, 1, 3)                  # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)                  # (B, K, T, hd)
    vt = v.transpose(0, 2, 1, 3)
    bq, bk = _pick_block(S), _pick_block(T)
    grid = (B, H, S // bq, T // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, softcap=softcap,
                          window=window, bq=bq, bk=bk, n_k=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
