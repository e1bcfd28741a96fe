"""The q/k norm-and-RoPE kernel (interpret mode) against its oracle, the
blocks' XLA formulation ``dit.apply_rope(common.layernorm(...))``, and
the rule that routes a block through it: RoPE tables present and a
head size of whole 128 lanes.  Elsewhere the blocks trace the program
they traced before the kernel existed."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DiTConfig
from repro.kernels import ops
from repro.kernels import qk_norm_rope as qk
from repro.models import common, dit

AXES, THETA = (16, 56, 56), 10000.0


def _oracle(xs, scales, rope, n_heads):
    """The parent's path: each stream's projection LayerNormed per head
    by its own scale, the streams joined, then RoPE over the join."""
    normed = []
    for x, scale in zip(xs, scales, strict=True):
        b, s, d = x.shape
        normed.append(common.layernorm(
            x.reshape(b, s, n_heads, d // n_heads), scale=scale))
    return dit.apply_rope(jnp.concatenate(normed, axis=1), rope)


def _ulps(got, want):
    """Largest distance in bf16 ulps (the spacing at the larger value)."""
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    big = np.maximum(np.abs(g), np.abs(w))
    ulp = np.exp2(np.floor(np.log2(np.maximum(big, 2.0 ** -126))) - 7)
    return float(np.max(np.abs(g - w) / ulp))


def _streams(lengths, n_heads, hd, seed):
    keys = jax.random.split(jax.random.key(seed), 2 * len(lengths))
    xs = [(2.0 * jax.random.normal(k, (2, n, n_heads * hd)) + 0.5)
          .astype(jnp.bfloat16) for k, n in zip(keys, lengths)]
    scales = [(1.0 + 0.25 * jax.random.normal(k, (hd,))).astype(jnp.bfloat16)
              for k in keys[len(lengths):]]
    return xs, scales


def _rope(s_txt, grid, ref_grid=None, axes=AXES):
    return dit.rope_tables(dit.token_ids(s_txt, grid, ref_grid), axes, THETA)


def test_lane_tables_by_hand():
    cos = jnp.array([[1.0, 2.0]])
    sin = jnp.array([[3.0, 4.0]])
    c, sg = qk.lane_tables(cos, sin)
    assert c.tolist() == [[1.0, 1.0, 2.0, 2.0]]
    assert sg.tolist() == [[-3.0, 3.0, -4.0, 4.0]]
    assert c.dtype == sg.dtype == jnp.float32


@pytest.mark.pallas
@pytest.mark.parametrize("s_txt,grid,ref_grid", [
    pytest.param(16, (4, 4), (4, 4), id="text-image-reference"),
    # 24 + 64 + 36 = 124 tokens: no power of two, one block of all of them
    pytest.param(24, (8, 8), (6, 6), id="124-tokens"),
    # 32 + 256 + 256 = 544 tokens: a block of 512, then a partial one
    pytest.param(32, (16, 16), (16, 16), id="partial-block"),
    pytest.param(0, (8, 8), None, id="image-only"),
])
def test_one_stream_matches_the_oracle(s_txt, grid, ref_grid):
    rope = _rope(s_txt, grid, ref_grid)
    s = rope[0].shape[0]
    xs, scales = _streams([s], 2, 128, seed=s)
    got = qk.norm_rope(xs, scales, *rope, 2)
    assert got.shape == (2, 2, s, 128) and got.dtype == jnp.bfloat16
    want = _oracle(xs, scales, rope, 2)
    assert _ulps(got.transpose(0, 2, 1, 3), want) <= 1.0


@pytest.mark.pallas
@pytest.mark.parametrize("s_txt,grid,rows", [
    pytest.param(16, (4, 4), 16, id="aliased-16"),
    pytest.param(32, (8, 8), 32, id="aliased-32"),
    # 24 text tokens are no whole number of 16-row blocks: the two
    # streams are written apart and joined by a concatenate
    pytest.param(24, (4, 4), None, id="joined-by-concatenate"),
])
def test_dual_stream_join_matches_the_oracle(s_txt, grid, rows):
    """[text | image + reference], each stream with its own norm scale."""
    rope = _rope(s_txt, grid, grid)
    s_img = rope[0].shape[0] - s_txt
    assert qk.joint_rows([s_txt, s_img]) == rows
    xs, scales = _streams([s_txt, s_img], 2, 128, seed=s_txt)
    assert not bool(jnp.all(scales[0] == scales[1]))
    got = qk.norm_rope(xs, scales, *rope, 2)
    want = _oracle(xs, scales, rope, 2)
    assert _ulps(got.transpose(0, 2, 1, 3), want) <= 1.0
    # each stream's rows took its own scale
    swapped = _oracle(xs, scales[::-1], rope, 2)
    assert _ulps(got.transpose(0, 2, 1, 3), swapped) > 1.0


def test_joint_rows():
    assert qk.joint_rows([8704]) == qk.ROWS
    assert qk.joint_rows([100]) == 100
    assert qk.joint_rows([512, 8192]) == 512
    assert qk.joint_rows([256, 4096]) == 256
    assert qk.joint_rows([24, 64]) is None
    assert qk.joint_rows([77, 4096]) is None


def _cfg(d, heads, rope_axes=()):
    return DiTConfig(arch_id="block", n_layers=1, n_double=1, d_model=d,
                     n_heads=heads, d_ff=2 * d, patch_size=2, in_channels=4,
                     text_dim=0, n_text_tokens=0, rope_axes=rope_axes,
                     dtype="bfloat16")


def _count_kernel_calls(monkeypatch):
    calls = []
    kernel = ops.qk_norm_rope
    monkeypatch.setattr(ops, "qk_norm_rope",
                        lambda *a: calls.append(a) or kernel(*a))
    return calls


@pytest.mark.pallas
@pytest.mark.parametrize("heads,hd,with_rope,fused", [
    pytest.param(2, 128, True, True, id="rope-hd128"),
    pytest.param(2, 72, True, False, id="rope-hd72"),
    pytest.param(2, 128, False, False, id="no-rope-hd128"),
])
def test_blocks_take_the_kernel_by_rope_and_head_size(monkeypatch, heads, hd,
                                                      with_rope, fused):
    """On the Pallas backend a block calls the kernel once for q and once
    for k exactly where it has RoPE tables and hd % 128 == 0; its output
    is the XLA backend's (the kernel matches the oracle to the bit here,
    and below flash's threshold attention is the same einsum)."""
    d = heads * hd
    axes = {128: AXES, 72: (8, 32, 32)}[hd] if with_rope else ()
    cfg = _cfg(d, heads, axes)
    params = dit.random_params(cfg, 0)
    s_txt, grid = 16, (4, 4)
    rope = _rope(s_txt, grid, grid, axes) if with_rope else None
    s_img = 2 * grid[0] * grid[1]
    keys = jax.random.split(jax.random.key(3), 3)
    img = jax.random.normal(keys[0], (2, s_img, d), jnp.bfloat16)
    txt = jax.random.normal(keys[1], (2, s_txt, d), jnp.bfloat16)
    cond = jax.random.normal(keys[2], (2, d), jnp.bfloat16)
    single = jax.tree.map(lambda a: a[0], params["single"])
    double = jax.tree.map(lambda a: a[0], params["double"])
    joint = jnp.concatenate([txt, img], axis=1)

    def run():
        return (dit.single_block(single, joint, cond, cfg, rope),
                *dit.double_block(double, img, txt, cond, cfg, rope))

    monkeypatch.setenv("REPRO_KERNELS", "xla")
    want = run()
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    calls = _count_kernel_calls(monkeypatch)
    got = run()
    assert len(calls) == (4 if fused else 0)
    if fused:
        # single block: q, k of one stream; double: q, k of [txt | img]
        assert [len(c[0]) for c in calls] == [1, 1, 2, 2]
    for g, w in zip(got, want, strict=True):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def _single_block_before(params, x, cond, cfg, rope=None):
    """``dit.single_block`` as it was before the q/k kernel."""
    sh1, sc1, g1, sh2, sc2, g2 = dit._modulation(params["mod"], cond, 6)
    h = common.layernorm(x, cfg.norm_eps) * (1 + sc1) + sh1
    q, k, v = dit._qkv_heads(params["attn"], h, cfg.n_heads)
    q, k = dit.apply_rope(q, rope), dit.apply_rope(k, rope)
    x = x + g1 * dit._joint_attention(q, k, v, params["attn"]["wo"], x.dtype)
    h = common.layernorm(x, cfg.norm_eps) * (1 + sc2) + sh2
    y = jax.nn.gelu(h @ params["mlp"]["wi"].astype(x.dtype))
    x = x + g2 * (y @ params["mlp"]["wo"].astype(x.dtype))
    return x


@pytest.mark.parametrize("batch,seq,heads,hd", [
    pytest.param(4, 4096, 24, 128, id="flux"),
    pytest.param(8, 1024, 16, 72, id="dit-xl2"),
])
def test_single_block_without_rope_traces_the_program_it_did(
        monkeypatch, batch, seq, heads, hd):
    """FLUX-cut and DiT-XL/2 widths, served shapes, Pallas backend,
    ``rope=None``: the same jaxpr as the block before the kernel (flash
    and nothing new), the q/k kernel nowhere in it."""
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    d = heads * hd
    cfg = _cfg(d, heads)
    block = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype),
                         jax.eval_shape(lambda: dit.random_params(cfg, 0))
                         ["single"])
    x = jax.ShapeDtypeStruct((batch, seq, d), jnp.bfloat16)
    c = jax.ShapeDtypeStruct((batch, d), jnp.bfloat16)
    now, before = (str(jax.make_jaxpr(functools.partial(f, cfg=cfg))(
        block, x, c)) for f in (dit.single_block, _single_block_before))
    assert now == before
    assert "_flash" in now and "_qk_norm_rope" not in now


def test_heads_per_block_within_budget():
    """Blocks of the served Kontext shape: 512 tokens by 12 of 24 heads,
    1.5 MiB of bf16 in and out; a head size or count that fits no more
    than one head still gets one."""
    assert qk._heads_per_block(24, 512, 128, 2) == 12
    assert qk._heads_per_block(24, 512, 128, 4) == 8
    assert qk._heads_per_block(5, 512, 128, 2) == 5
    assert qk._heads_per_block(3, 8192, 256, 4) == 1
    assert 12 * 512 * 128 * 2 <= qk.BLOCK_BYTES
