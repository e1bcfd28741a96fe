"""Per-head LayerNorm and FLUX's pairwise RoPE on q or k in one pass.

Between a block's q (or k) projection and flash, the XLA formulation
(``models.dit.apply_rope`` of ``common.layernorm``) takes a statistics
pass, an f32 normalised tensor in a token-minor layout, a relayout copy
of it, the rotation over an ``(hd/2, 2)`` reshape and a copy into
flash's ``[B, H, S, hd]``.  This kernel reads the bf16 projection
``[B, S, H·hd]`` once and writes bf16 ``[B, H, S, hd]`` once, the layout
flash's token-major blocks read, so the transposes between the two
cancel.

Arithmetic is the XLA formulation's: mean, variance, ``rsqrt`` and the
scale in f32, the normalised value rounded to the input dtype, then the
rotation in f32 from tables expanded to ``[S, hd]`` (``lane_tables``):
``out = y·C + swap(y)·Sg``, C the cosine repeated over each channel
pair and Sg the sine with the pair's signs ``(-, +)``; ``swap``
exchanges the channels of each pair, two lane rolls and a parity
select.

Blocks: ``ROWS`` tokens by as many heads as keep each block within
``BLOCK_BYTES``; the grid runs heads innermost, then batch, so a table
block is fetched once per row block.  A trailing partial row block is
served by the pipeline's edge handling (each row is normalised alone).
The joint ``[text | image]`` sequence of a dual-stream block is written
by one call per stream into one buffer, the second aliased to the
first's output.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import SUBLANES, _block

# tokens per block, and the most bytes of one input block
ROWS = 512
BLOCK_BYTES = 2 * 1024 * 1024
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def lane_tables(cos: jnp.ndarray, sin: jnp.ndarray):
    """(C, Sg) [S, hd] f32 from RoPE's (cos, sin) [S, hd/2]: C[:, 2j] =
    C[:, 2j+1] = cos[:, j]; Sg[:, 2j] = -sin[:, j], Sg[:, 2j+1] = sin[:, j]."""
    def pairs(a, b):
        return jnp.stack([a, b], axis=-1).astype(jnp.float32) \
            .reshape(a.shape[0], -1)
    return pairs(cos, cos), pairs(-sin, sin)


def _heads_per_block(n_heads: int, rows: int, hd: int, itemsize: int) -> int:
    """The most heads, dividing ``n_heads``, whose block fits BLOCK_BYTES."""
    fit = max(1, BLOCK_BYTES // (rows * hd * itemsize))
    return max(g for g in range(1, min(fit, n_heads) + 1) if n_heads % g == 0)


def _kernel(x_ref, scale_ref, c_ref, s_ref, *refs, hg: int, hd: int,
            eps: float):
    o_ref = refs[-1]           # after the aliased buffer, where there is one
    c, sg = c_ref[...], s_ref[...]                      # [rows, hd] f32
    scale = scale_ref[...].astype(jnp.float32)          # [1, hd]
    lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    # the lane each roll brings in, so the swap holds whichever way a
    # positive shift turns
    from_1 = pltpu.roll(lane, 1, 1)
    take_1 = from_1 == jnp.bitwise_xor(lane, 1)
    for h in range(hg):
        x = x_ref[0, :, h * hd:(h + 1) * hd].astype(jnp.float32)
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps) * scale
        y = y.astype(x_ref.dtype).astype(jnp.float32)
        swapped = jnp.where(take_1, pltpu.roll(y, 1, 1),
                            pltpu.roll(y, hd - 1, 1))
        o_ref[0, h] = (y * c + swapped * sg).astype(o_ref.dtype)


def _call(x, scale, c, sg, n_heads, rows, row0, out, eps, interpret):
    """Rows ``[row0, row0 + Sx)`` of ``[B, H, S, hd]`` from x [B, Sx, H·hd]
    (S the tables' length), into ``out`` if given, else a new buffer."""
    b, sx, _ = x.shape
    s, hd = c.shape
    assert row0 % rows == 0, (row0, rows)
    hg = _heads_per_block(n_heads, rows, hd, x.dtype.itemsize)
    off = row0 // rows
    operands = [x, scale.reshape(1, hd), c, sg]
    in_specs = [
        pl.BlockSpec((1, rows, hg * hd), lambda si, bi, h: (bi, si, h)),
        pl.BlockSpec((1, hd), lambda si, bi, h: (0, 0)),
        pl.BlockSpec((rows, hd), lambda si, bi, h: (si + off, 0)),
        pl.BlockSpec((rows, hd), lambda si, bi, h: (si + off, 0)),
    ]
    aliases = {}
    if out is not None:
        # the earlier streams' buffer, left in HBM and written in place
        operands.append(out)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {len(operands) - 1: 0}
    return pl.pallas_call(
        functools.partial(_kernel, hg=hg, hd=hd, eps=eps),
        grid=(pl.cdiv(sx, rows), b, n_heads // hg),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hg, rows, hd),
                               lambda si, bi, h: (bi, h, si + off, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_heads, s, hd), x.dtype),
        input_output_aliases=aliases,
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(*operands)


def joint_rows(lengths) -> int | None:
    """Rows per block at which streams of ``lengths`` tokens can be
    written one after another into one buffer, so that every stream but
    the first starts on a block: the common divisor of all lengths but
    the last, itself if it is at most ``ROWS``, else its largest
    power-of-two divisor up to ``ROWS``; None where that is no whole
    number of packed sublanes.  A single stream takes ``ROWS``, or all
    of its tokens if fewer."""
    *heads, last = lengths
    if not heads:
        return min(last, ROWS)
    rows = _block(functools.reduce(math.gcd, heads), ROWS)
    return rows if rows % SUBLANES == 0 else None


def norm_rope(xs, scales, cos, sin, n_heads: int, eps: float = 1e-6,
              interpret: bool = True):
    """Streams ``xs`` ([B, S_i, H·hd] projections, joined along the tokens
    in this order), each LayerNormed per head by its own ``scales[i]``
    [hd], then rotated by RoPE's (cos, sin) [ΣS_i, hd/2] of the joined
    sequence -> [B, H, ΣS_i, hd] in the input dtype.

    Where the streams cannot be written into one buffer at whole blocks
    (``joint_rows``), each is written alone and the parts joined by a
    concatenate."""
    c, sg = lane_tables(cos, sin)
    lengths = [x.shape[1] for x in xs]
    rows = joint_rows(lengths)
    if rows is None:
        parts, row0 = [], 0
        for x, scale, n in zip(xs, scales, lengths, strict=True):
            part = slice(row0, row0 + n)
            parts.append(norm_rope([x], [scale], cos[part], sin[part],
                                   n_heads, eps, interpret=interpret))
            row0 += n
        return jnp.concatenate(parts, axis=2)
    out, row0 = None, 0
    for x, scale, n in zip(xs, scales, lengths, strict=True):
        out = _call(x, scale, c, sg, n_heads, rows, row0, out, eps,
                    interpret)
        row0 += n
    return out
