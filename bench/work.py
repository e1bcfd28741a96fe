"""Operations and bytes the work needs, from shapes and dtypes alone.

Every count here is what the algorithm requires for one call or one
step, never what a particular kernel happens to move: a kernel that
reads its input twice, or computes in f32 what the configuration keeps
in bf16, reads as below its roofline, not as doing more work.  A
multiply-add is two operations.
"""
from __future__ import annotations

from typing import NamedTuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2,
               "float8_e4m3fn": 1, "int8": 1}


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other):
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, n):
        return Work(self.flops * n, self.bytes * n)


def roofline_s(work: Work, peak: dict) -> tuple:
    """(least seconds the chip needs, "compute" | "memory")."""
    compute = work.flops / peak["bf16_flops_per_s"]
    memory = work.bytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def image_tokens(model: dict, image_px: int, vae_factor: int = 8) -> int:
    side = image_px // vae_factor // model["patch_size"]
    return side * side


def kept_bins(s: int, rho: float) -> int:
    """Low-band width of a DCT band split keeping a ``rho`` fraction of
    ``s`` token frequencies (at least one bin)."""
    return min(max(int(round(s * rho)), 1), s)


# --- kernels: one call ---------------------------------------------------

def flash(b: int, s: int, heads: int, hd: int, dtype: str) -> Work:
    """Non-causal self-attention over ``s`` tokens: QKᵀ and PV, and Q,
    K, V in with O out."""
    return Work(4.0 * b * heads * s * s * hd,
                4.0 * b * s * heads * hd * DTYPE_BYTES[dtype])


def band_split(b: int, s: int, d: int, m: int, dtype: str) -> Work:
    """Analysis ``B·x`` to ``m`` low coefficients and the high band
    ``x − Bᵀ·low``: the feature in, low and high out."""
    return Work(4.0 * b * m * s * d + b * s * d,
                (2.0 * b * s * d + b * m * d) * DTYPE_BYTES[dtype])


def freqca_predict(b: int, s: int, d: int, m: int, k: int, dtype: str,
                   calls: int = 1) -> Work:
    """Cached-step reconstruction ``Bᵀ·low + Σ_k w_k·high_k`` over ``b``
    lanes in ``calls`` calls: the K high-band history entries and the
    low coefficients of each lane and the synthesis basis of each call
    in, the predicted feature out."""
    return Work(2.0 * b * s * m * d + 2.0 * b * k * s * d,
                (b * k * s * d + b * m * d + calls * s * m + b * s * d)
                * DTYPE_BYTES[dtype])


# --- model steps: one image ----------------------------------------------

def _final_layer_flops(model: dict, s: int) -> float:
    d = model["d_model"]
    pdim = model["patch_size"] ** 2 * model["in_channels"]
    return 2.0 * d * 2 * d + 2.0 * s * d * pdim


def _time_flops(model: dict) -> float:
    d = model["d_model"]
    return 2.0 * (model["time_embed_dim"] * d + d * d)


def forward_flops(model: dict, s: int) -> float:
    """The ``dit`` family's count (``bench/programs/dit.py``): one
    denoiser forward of one image over ``s`` image tokens through the
    single-stream blocks (its served path carries no text, so the
    dual-stream blocks do not run): patch embedding, time embedding,
    per block the 6-way modulation, Q/K/V/O and MLP projections and
    attention, then the final layer."""
    d, f, heads = model["d_model"], model["d_ff"], model["n_heads"]
    pdim = model["patch_size"] ** 2 * model["in_channels"]
    block = (2.0 * d * 6 * d                     # modulation
             + 2.0 * 4 * s * d * d               # Q, K, V, O
             + 2.0 * 2 * s * d * f               # MLP in and out
             + flash(1, s, heads, d // heads, "bfloat16").flops)
    return (2.0 * s * pdim * d + _time_flops(model)
            + model["n_layers"] * block + _final_layer_flops(model, s))


def cache_k(policy: dict) -> int:
    return policy.get("high_order", 2) + 1


def full_step_flops(forward: float, model: dict, s: int,
                    policy: dict) -> float:
    """A full step: ``forward``, the operations of one forward by the
    family's count, and for FreqCa the band split that fills the cache
    over the ``s`` image tokens."""
    flops = forward
    if policy["name"] == "freqca":
        m = kept_bins(s, policy["rho"])
        flops += band_split(1, s, model["d_model"], m, model["dtype"]).flops
    return flops


def cached_step_flops(model: dict, s: int, policy: dict) -> float:
    """A cached step: the reconstruction of the feature and the final
    layer on it (with its time embedding)."""
    m = kept_bins(s, policy["rho"])
    return (freqca_predict(1, s, model["d_model"], m, cache_k(policy),
                           model["dtype"]).flops
            + _time_flops(model) + _final_layer_flops(model, s))


def image_flops(forward: float, model: dict, s: int, policy: dict,
                n_full: int, n_steps: int) -> float:
    """Useful operations of one image whose schedule ran ``n_full`` full
    steps of ``n_steps``, a forward costing ``forward``."""
    flops = n_full * full_step_flops(forward, model, s, policy)
    if n_steps > n_full:
        flops += (n_steps - n_full) * cached_step_flops(model, s, policy)
    return flops
