"""Compile-only rehearsals of the served kernels for a TPU v5e chip.

Each case lowers one Pallas kernel at the FLUX.1-dev serving widths
(1024 px: 4096 image tokens, d=3072; 4608 joint tokens with the 512
text tokens; 24 heads of 128), or flash at DiT-XL/2's (1024 tokens, 16
heads of 72), and compiles it for one chip of a
described ``v5e:2x2`` topology — no chip attached.  The compiler then
refuses what interpret mode cannot see: blocks not aligned to the
tiling, and more VMEM than a kernel may use.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.
"""
import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import frequency
from repro.kernels import dct as dct_kernel
from repro.kernels import flash_attention as fa
from repro.kernels import freqca_fused

S_IMG, S_JOINT, D, HEADS, HD = 4096, 4096 + 512, 3072, 24, 128
# FLUX.1-Kontext: 512 text tokens, a 1024 px image and its reference
S_KONTEXT, ROPE_AXES = 512 + 2 * 4096, (16, 56, 56)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rho", [1 / 16, 1 / 4])
def test_band_split_spectral_compiles_at_flux_width(one_chip, rho):
    fn = functools.partial(dct_kernel.band_split_spectral, rho=rho,
                           interpret=False)
    _compile(fn, one_chip, ((4, S_IMG, D), jnp.bfloat16))


def test_fused_spectral_predict_compiles_at_flux_width(one_chip):
    m = frequency.spectral_kept_bins(S_IMG, 1 / 16, "dct")
    fn = functools.partial(freqca_fused.freqca_predict_fused_spectral,
                           interpret=False)
    _compile(fn, one_chip,
             ((4, m, D), jnp.float32), ((S_IMG, m), jnp.float32),
             ((4, 3, S_IMG, D), jnp.float32), ((4, 3), jnp.float32))


@pytest.mark.parametrize("batch,seq", [(4, S_IMG), (1, S_JOINT)])
def test_noncausal_flash_compiles_at_flux_width(one_chip, batch, seq):
    fn = functools.partial(fa.flash_attention, q_per_kv=1, causal=False,
                           interpret=False)
    shape = ((batch, seq, HEADS, HD), jnp.bfloat16)
    _compile(fn, one_chip, shape, shape, shape)


def test_noncausal_flash_compiles_at_dit_xl2_width(one_chip):
    """DiT-XL/2 at 512 px as served: B=8, S=1024, 16 heads of 72 (not a
    whole 128 lanes), bf16, at the default tiles."""
    fn = functools.partial(fa.flash_attention, q_per_kv=1, causal=False,
                           interpret=False)
    shape = ((8, 1024, 16, 72), jnp.bfloat16)
    _compile(fn, one_chip, shape, shape, shape)


def _block_cfg(heads, hd, rope):
    from repro.configs.base import DiTConfig
    d = heads * hd
    return DiTConfig(arch_id="block", n_layers=1, n_double=1, d_model=d,
                     n_heads=heads, d_ff=4 * d, patch_size=2, in_channels=4,
                     text_dim=0, n_text_tokens=0,
                     rope_axes=ROPE_AXES if rope else (), dtype="bfloat16")


def _compile_block(fn, sharding, params, *shapes, rope_tokens=0):
    """``fn(params, *activations, cond[, rope])`` compiled for one chip;
    the activations' layouts in and out left to the compiler, as a
    scan's carry is, so the program holds no copy at the block's
    edges."""
    from jax.experimental.layout import Format, Layout
    auto = Format(Layout.AUTO, sharding)
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]
    # params, the activations, then the conditioning vector
    in_shardings = [sharding] + [auto] * (len(shapes) - 1) + [sharding]
    if rope_tokens:
        half = jax.ShapeDtypeStruct((rope_tokens, HD // 2), jnp.float32)
        args.append((half, half))
        in_shardings.append(sharding)
    return jax.jit(fn, in_shardings=tuple(in_shardings),
                   out_shardings=auto).lower(
        params, *args).compile().as_text()


def _qk_path(text, qk_elems):
    """Kernel names in order; the copies under ``jit(_flash)``; and every
    copy and every f32 array of at least ``qk_elems`` elements that the
    program writes (not the instructions inside a fusion)."""
    import re
    kernels, flash_copies, big_copies, big_f32 = [], [], [], []
    fused = False
    for line in text.splitlines():
        if re.match(r"\S", line):
            fused = line.startswith("%fused") or "fusion" in line.split(
                "(")[0]
        name = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (\w+)\[([\d,]*)\]",
                        line)
        if name is None:
            continue
        if "tpu_custom_call" in line:
            kernels.append(re.sub(r"\.\d+$", "", name.group(1)))
            continue
        elems = math.prod(int(n) for n in name.group(3).split(",") if n)
        if name.group(1).startswith("copy"):
            if "jit(_flash)" in line:
                flash_copies.append(line.strip()[:160])
            if elems >= qk_elems:
                big_copies.append(line.strip()[:160])
        elif not fused and name.group(2) == "f32" and elems >= qk_elems:
            big_f32.append(line.strip()[:160])
    return kernels, flash_copies, big_copies, big_f32


@pytest.mark.parametrize("batch,seq,heads,hd,rope", [
    pytest.param(8, 1024, 16, 72, False, id="dit-xl2"),
    pytest.param(4, S_IMG, HEADS, HD, False, id="flux"),
    pytest.param(4, S_KONTEXT, HEADS, HD, True, id="kontext"),
])
def test_single_block_feeds_flash_without_relayout(one_chip, monkeypatch,
                                                   batch, seq, heads, hd,
                                                   rope):
    """One single block as served, bf16 with the Pallas kernels: the
    q/k/v projections reach the flash kernel, and its output the
    out-projection, with no layout copy under ``jit(_flash)``.  At head
    size 72 that holds because the kernel reads and writes the
    head-dim-major layout XLA gives the projections.  With RoPE tables
    (Kontext: 512 text + 2 x 4096 image and reference tokens) q and k
    each take one pass of the norm-and-RoPE kernel straight into flash's
    layout: no copy and no f32 array of q's size anywhere in the block."""
    from repro.models import dit
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "0")
    cfg = _block_cfg(heads, hd, rope)
    block = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype),
        jax.eval_shape(lambda: dit.random_params(cfg, 0))["single"])
    text = _compile_block(
        lambda p, x, c, *r: dit.single_block(p, x, c, cfg, *r), one_chip,
        block, (batch, seq, cfg.d_model), (batch, cfg.d_model),
        rope_tokens=seq if rope else 0)
    kernels, flash_copies, big_copies, big_f32 = _qk_path(
        text, batch * seq * cfg.d_model)
    assert flash_copies == []
    if rope:
        assert kernels == ["_qk_norm_rope", "_qk_norm_rope", "_flash"]
        assert big_copies == [] and big_f32 == []
    else:
        assert kernels == ["_flash"]


def test_double_block_writes_the_joined_qk_for_flash(one_chip, monkeypatch):
    """Kontext's dual-stream block at bucket 4 (512 text tokens, 8192
    image and reference tokens): q and k of each stream normed and
    rotated straight into the joined ``[text | image]`` sequence flash
    reads, so no concatenate copy of q or k, no other copy and no f32
    array of q's size."""
    from repro.models import dit
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "0")
    cfg = _block_cfg(HEADS, HD, True)
    block = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype),
        jax.eval_shape(lambda: dit.random_params(cfg, 0))["double"])
    s_txt = S_KONTEXT - 2 * S_IMG
    text = _compile_block(
        lambda p, i, t, c, r: dit.double_block(p, i, t, c, cfg, r),
        one_chip, block, (4, 2 * S_IMG, D), (4, s_txt, D), (4, D),
        rope_tokens=S_KONTEXT)
    kernels, flash_copies, big_copies, big_f32 = _qk_path(
        text, 4 * 2 * S_IMG * D)
    assert kernels == ["_qk_norm_rope"] * 4 + ["_flash"]
    assert flash_copies == big_copies == big_f32 == []


def test_step_scopes_leave_the_kernel_names(one_chip, monkeypatch):
    """The sampler's step scopes reach every kernel's op metadata and
    leave its instruction named after the jitted function that calls it,
    the name the benchmark's trace reduction finds it by: a FreqCa
    engine's whole sampler at 1024 tokens, d=256, compiled for one chip."""
    import re

    from repro.configs.base import DiTConfig
    from repro.core import policies
    from repro.diffusion import sampler
    from repro.models import dit
    from repro.serving.engine import DiffusionEngine
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "0")
    cfg = DiTConfig(arch_id="tiny", n_layers=1, d_model=256, n_heads=2,
                    d_ff=512, patch_size=2, in_channels=4, text_dim=0,
                    n_text_tokens=0, dtype="bfloat16")
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: dit.random_params(cfg, 0)))
    full_fn, from_crf_fn = dit.denoiser(cfg)
    eng = DiffusionEngine(full_fn, from_crf_fn, params, (64, 64, 4),
                          (1024, 256), policies.FreqCaPolicy(interval=5),
                          n_steps=6, max_batch=2)
    x = jax.ShapeDtypeStruct((2, 64, 64, 4), jnp.float32, sharding=one_chip)
    text = eng._jit_run.lower(params, x, eng.policy,
                              eng.crf_shape).compile().as_text()
    kernels = {}
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            name = line.split(" = ")[0].strip().lstrip("%")
            scope = re.search(r'op_name="([^"]+)"', line).group(1)
            kernels[re.sub(r"\.\d+$", "", name)] = scope
    assert set(kernels) == {"_flash", "_band_split_spectral_pallas",
                            "_freqca_predict_spectral_pallas"}
    assert f"/{sampler.FULL_STEP}/" in kernels["_flash"]
    assert f"/{sampler.FULL_STEP}/" in kernels["_band_split_spectral_pallas"]
    assert f"/{sampler.CACHED_STEP}/" \
        in kernels["_freqca_predict_spectral_pallas"]


def test_kontext_cut_full_step_compiles_with_flash_named(one_chip,
                                                         monkeypatch):
    """FLUX.1-Kontext-dev cut to 4 + 8 blocks at its published widths,
    one full denoiser step of one lane with its conditioning (512 text
    tokens, pooled vector, guidance, a 1024 px reference: 8704 joint
    tokens): both block kinds call the flash kernel, named ``_flash``,
    inside their ``dit.*_blocks`` scopes."""
    import dataclasses
    import re

    from repro.configs import flux1_kontext_dev
    from repro.models import dit
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "0")
    cfg = dataclasses.replace(flux1_kontext_dev.CONFIG, n_double=4,
                              n_layers=8)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: dit.random_params(cfg, 0)))
    full_fn, _ = dit.denoiser(cfg)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cond = {"txt": arg((1, 512, 4096)), "vec": arg((1, 768)),
            "guidance": arg((1,)), "ref_latents": arg((1, 128, 128, 16))}
    text = jax.jit(full_fn).lower(params, arg((1, 128, 128, 16)),
                                  arg(()), cond).compile().as_text()
    scopes = [re.search(r'op_name="([^"]+)"', line).group(1)
              for line in text.splitlines()
              if "tpu_custom_call" in line
              and re.match(r"\s*%?_flash(\.\d+)? = ", line)]
    assert len(scopes) == 2
    assert sum("dit.double_blocks" in s for s in scopes) == 1
    assert sum("dit.single_blocks" in s for s in scopes) == 1
