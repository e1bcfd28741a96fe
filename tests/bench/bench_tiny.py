"""A tiny cell of the benchmark for CPU tests: the DiT family at a few
dozen widths, with the limits of a real cell."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell as cell_lib  # noqa: E402

MODEL = {"n_double": 0, "n_layers": 2, "d_model": 64, "n_heads": 4,
         "d_ff": 128, "patch_size": 2, "in_channels": 4, "text_dim": 0,
         "n_text_tokens": 0, "time_embed_dim": 256, "norm_eps": 1e-6,
         "dtype": "bfloat16"}
FREQCA = {"name": "freqca", "interval": 5, "method": "dct", "rho": 0.0625,
          "low_order": 0, "high_order": 2}


def cell(policy=FREQCA, dtype="bfloat16", limits_of=None, trace=False,
         **traffic):
    """A Cell at 64 px (16 tokens) with max batch 4, by default with no
    backlog, so that every bucket is warmed and cut."""
    model = dict(MODEL, dtype=dtype)
    t = {"image_px": 64, "policy": policy, "rate_per_s": 20.0, "backlog": 0,
         "edit_every": 4, "edit_strength": 0.5}
    t.update(traffic)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    limits = {"latent_rel_err_max": 0.1, "check_requests": 3}
    if limits_of:
        limits = json.loads((ROOT / "bench" / "limits"
                             / f"{limits_of}.json").read_text())
    return cell_lib.Cell(
        name="tiny", chips=1,
        config={"name": "tiny", "family": "dit", "model": model,
                "engine": {"max_batch": 4, "max_wait_s": 0.05,
                           "n_steps": 50}},
        traffic=t, limits=limits,
        metrics=bench["per_layer" if trace else "end_to_end"])
