"""Flash attention (GQA) Pallas kernel — the prefill/train hot spot.

Grid: (batch x kv_heads x q_groups, q blocks, kv blocks) with the kv
dimension SEQUENTIAL; the online-softmax state (acc, running max m,
normaliser l) lives in VMEM scratch across kv steps and the output tile
is written once on the last step — the TPU-native version of the
jnp blockwise path in ``models/attention.blockwise_sdpa`` (its oracle).

Two layouts of Q, K, V and O in HBM, chosen by the head size alone:

* token-major, ``[.., S, hd]`` blocks with hd on the lanes, where hd is
  a multiple of 128 (``LANES``): each row fills whole lanes;
* head-dim-major, ``[.., hd, S]`` blocks with the tokens on the lanes,
  at any other hd.  XLA lays out a head that does not fill 128 lanes
  (DiT-XL/2's 72) as ``[.., hd, S]`` already, where ``[S, hd]`` would
  pad it to 128 lanes, so q, k and v reach the kernel, and its output
  reaches the out-projection, as bitcasts; token-major blocks there
  cost a relayout copy of k, v and o per call.  The kernel holds the
  logits transposed, ``[kv block, q block]``, m and l as ``[1, q
  block]`` rows and the f32 acc as ``[hd, q block]``.

The MXU takes Q, K and V at their input dtype with f32 accumulation
(a product of two bf16 values is exact in f32); only the probabilities
are rounded, to V's dtype, for the PV product.  The logits, m, l, acc
and the final division stay in f32.  Tiles follow from the call's shape
(``tiles``): each grid step carries a fixed cost, so the blocks are as
large as v5e's default scoped VMEM allows.

This is what the roofline's "memory term is an upper bound" note refers
to (EXPERIMENTS.md §Roofline): the XLA-level blockwise path materialises
[qb x kb] logits tiles at fusion boundaries, while this kernel keeps
them in VMEM/VREGs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# largest q block, and largest [q block x kv block] logits tile (4 MiB of
# f32): at both served shapes, the pair within VMEM_BUDGET that an
# on-chip sweep (tools/flash_sweep.py) timed fastest
Q_CAP, TILE_CAP = 2048, 1 << 20
# v5e's default scoped VMEM per kernel (not raised: ``vmem_limit_bytes``)
VMEM_BUDGET = 16 * 1024 * 1024
# bf16 rows a (16, 128) VMEM tile packs (f32 needs 8: counted as 16)
SUBLANES = 16
# contract the last dims of q [qb, hd] and k [kb, hd]: q·kᵀ with no transpose
_QK_DIMS = (((1,), (1,)), ((), ()))
# contract the first dims of kᵀ [hd, kb] and qᵀ [hd, qb]: the logits
# transposed, [kb, qb]
_KQ_DIMS = (((0,), (0,)), ((), ()))
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _block(n: int, cap: int) -> int:
    """``n`` itself if it fits the cap, else its largest power-of-two
    divisor up to the cap."""
    if n <= cap:
        return n
    return math.gcd(n, 1 << (cap.bit_length() - 1))


def _blocks(s: int, t: int) -> tuple[int, int]:
    """The q block up to ``Q_CAP``, then the kv block up to what keeps
    the logits tile within ``TILE_CAP``."""
    bq = _block(s, Q_CAP)
    return bq, _block(t, TILE_CAP // bq)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_bytes(bq: int, bk: int, hd: int, dtype) -> int:
    """VMEM the kernel holds at blocks (bq, bk) in the layout ``hd``
    picks: double-buffered Q, K, V and O blocks, the f32 acc, m and l,
    and the f32 logits and probabilities.  Token-major blocks are rows
    of hd lanes, with m and l replicated over 128 lanes; head-dim-major
    blocks are hd rows (rounded up to ``SUBLANES``) of the block length
    (rounded up to 128 lanes), with m and l one row each (8 sublanes).
    Conservative: over blocks of 256 to 4096 in bf16 and f32, the v5e
    compiler accepted every pair this puts under ``VMEM_BUDGET`` (and
    some above it), at head sizes 128 and 256 token-major and 40, 64,
    72 and 80 head-dim-major."""
    item = jnp.dtype(dtype).itemsize
    if hd % LANES == 0:
        blocks = 2 * (2 * bq + 2 * bk) * hd * item
        scratch = bq * hd * 4 + 2 * bq * LANES * 4
        return blocks + scratch + 2 * bq * bk * 4
    lq, lk = _round_up(bq, LANES), _round_up(bk, LANES)
    blocks = 2 * (2 * lq + 2 * lk) * _round_up(hd, SUBLANES) * item
    scratch = (_round_up(hd, 8) + 2 * 8) * lq * 4
    return blocks + scratch + 2 * _round_up(bk, 8) * lq * 4


def _halve(n: int, b: int) -> int:
    """``b`` halved if the half still divides ``n`` in whole lanes."""
    h = b // 2
    return h if b % 2 == 0 and h % LANES == 0 and n % h == 0 else b


def tiles(s: int, t: int, hd: int, dtype) -> tuple[int, int]:
    """(block_q, block_k) for ``s`` queries over ``t`` keys of head size
    ``hd``: the largest power-of-two divisor of each length up to its
    cap (the whole length if it fits; ``_blocks``), halved — kv first —
    while ``vmem_bytes`` exceeds the default scoped VMEM."""
    bq, bk = _blocks(s, t)
    while vmem_bytes(bq, bk, hd, dtype) > VMEM_BUDGET:
        nk = _halve(t, bk)
        if nk != bk:
            bk = nk
            continue
        nq = _halve(s, bq)
        if nq == bq:
            break
        bq = nq
    return bq, bk


def dispatch_ok(s: int) -> bool:
    """Self-attention lengths the default tiles serve: each block is the
    whole sequence or a whole number of 128-row lanes, so no length
    falls back to tiny steps.  ``tiles`` only ever halves a block to
    another such block, so the rule holds at every head size and dtype
    (every length the former fixed 128-blocks took qualifies)."""
    return all(b == s or b % LANES == 0 for b in _blocks(s, s))


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] statistic as [rows, n]."""
    reps, rem = divmod(n, LANES)
    if rem == 0:
        return x if reps == 1 else jnp.tile(x, (1, reps))
    if reps == 0:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _keep(q_pos, k_pos, causal: bool, window: int):
    """Where the query at ``q_pos`` attends to the key at ``k_pos``."""
    keep = [k_pos <= q_pos] if causal else []
    if window > 0:
        keep.append(k_pos > q_pos - window)
    return functools.reduce(jnp.logical_and, keep)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, causal: bool, window: int, nk: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                              # [qb, hd]
    k = k_ref[0, 0]                              # [kb, hd]
    v = v_ref[0, 0]                              # [kb, hd]
    qb, kb = q.shape[0], k.shape[0]

    logits = jax.lax.dot_general(q, k, _QK_DIMS,
                                 preferred_element_type=jnp.float32) * scale
    if causal or window > 0:
        q_pos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        k_pos = ki * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        logits = jnp.where(_keep(q_pos, k_pos, causal, window), logits,
                           NEG_INF)

    m_prev = m_ref[...]                          # [qb, 128], lanes equal
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - _lanes(m_new, kb))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * _lanes(corr, acc_ref.shape[1]) + \
        jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / _lanes(l, acc_ref.shape[1])
                       ).astype(o_ref.dtype)


def _flash_kernel_hd_major(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                           l_ref, *, causal: bool, window: int, nk: int,
                           scale: float):
    """``_flash_kernel`` over head-dim-major blocks: qᵀ [hd, qb], kᵀ and
    vᵀ [hd, kb] in, oᵀ [hd, qb] out; the softmax runs down the columns
    of the [kb, qb] logits."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                 # [hd, qb]
    k = k_ref[0]                                 # [hd, kb]
    v = v_ref[0]                                 # [hd, kb]
    qb, kb = q.shape[1], k.shape[1]

    logits = jax.lax.dot_general(k, q, _KQ_DIMS,
                                 preferred_element_type=jnp.float32) * scale
    if causal or window > 0:
        k_pos = ki * kb + jax.lax.broadcasted_iota(jnp.int32, (kb, qb), 0)
        q_pos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (kb, qb), 1)
        logits = jnp.where(_keep(q_pos, k_pos, causal, window), logits,
                           NEG_INF)

    m_prev = m_ref[...]                          # [1, qb]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=0, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=0, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + \
        jnp.dot(v, p.astype(v.dtype), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, q_per_kv: int, causal: bool = True,
                    window: int = 0, q_block: int | None = None,
                    kv_block: int | None = None, interpret: bool = True):
    """q: [B, S, Hq, hd]; k, v: [B, T, Hkv, hd] -> [B, S, Hq, hd].

    Token-major where hd is a multiple of 128, head-dim-major otherwise
    (the module docstring).  ``q_block``/``kv_block`` of None take
    ``tiles``' blocks; an explicit block is clipped to the sequence."""
    s, hd = q.shape[1], q.shape[3]
    t = k.shape[1]
    auto_q, auto_k = tiles(s, t, hd, q.dtype)
    qb = auto_q if q_block is None else min(q_block, s)
    kb = auto_k if kv_block is None else min(kv_block, t)
    assert s % qb == 0 and t % kb == 0, (s, t, qb, kb)
    layout = _token_major if hd % LANES == 0 else _hd_major
    return layout(q, k, v, q_per_kv, causal, window, qb, kb, interpret)


def _token_major(q, k, v, g, causal, window, qb, kb, interpret):
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    nq, nk = s // qb, t // kb
    scale = 1.0 / math.sqrt(hd)

    # layout: fold (b, kv_head, group) into one parallel axis; repeat K/V
    # per group via index mapping (no materialised copy)
    qg = q.reshape(b, s, hkv, g, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(b * hkv * g, nq, qb, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(b * hkv, nk, kb, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(b * hkv, nk, kb, hd)

    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               nk=nk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * hkv * g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, hd), lambda i, qi, ki: (i, qi, 0, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda i, qi, ki: (i // g, ki, 0, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda i, qi, ki: (i // g, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, hd),
                               lambda i, qi, ki: (i, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv * g, nq, qb, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, hd), jnp.float32),
            pltpu.VMEM((qb, LANES), jnp.float32),
            pltpu.VMEM((qb, LANES), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(qg, kg, vg)
    return out.reshape(b, hkv, g, s, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(b, s, hq, hd)


def _hd_major(q, k, v, g, causal, window, qb, kb, interpret):
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    nk = t // kb

    # [B·Hkv·g, hd, S] and [B·Hkv, hd, T]: the layout XLA gives the
    # projections at such a head size, so these transposes are bitcasts
    qt = q.reshape(b, s, hkv, g, hd).transpose(0, 2, 3, 4, 1) \
        .reshape(b * hkv * g, hd, s)
    kt = k.transpose(0, 2, 3, 1).reshape(b * hkv, hd, t)
    vt = v.transpose(0, 2, 3, 1).reshape(b * hkv, hd, t)

    kernel = functools.partial(_flash_kernel_hd_major, causal=causal,
                               window=window, nk=nk,
                               scale=1.0 / math.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid=(b * hkv * g, s // qb, nk),
        in_specs=[
            pl.BlockSpec((1, hd, qb), lambda i, qi, ki: (i, 0, qi)),
            pl.BlockSpec((1, hd, kb), lambda i, qi, ki: (i // g, 0, ki)),
            pl.BlockSpec((1, hd, kb), lambda i, qi, ki: (i // g, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, hd, qb), lambda i, qi, ki: (i, 0, qi)),
        out_shape=jax.ShapeDtypeStruct((b * hkv * g, hd, s), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hd, qb), jnp.float32),
            pltpu.VMEM((1, qb), jnp.float32),
            pltpu.VMEM((1, qb), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, hkv, g, hd, s).transpose(0, 4, 1, 2, 3) \
        .reshape(b, s, hq, hd)
