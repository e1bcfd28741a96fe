"""Sharding integration on a small host-device mesh, in subprocesses
(XLA device count is locked at first jax init, so the 8-device flag
must not leak into the other tests):

* lower + compile StepSpecs for representative assigned architectures;
* end-to-end **grouped serving** through the bucketed DiffusionEngine
  on a real 8-way mesh — policy-homogeneous cuts execute with the
  batch sharded over the 4-way data axis (placement asserted shard by
  shard), requests conserved, finite outputs.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
from repro.launch import steps as steps_lib
from repro.roofline import hlo_analysis

arch, shape = sys.argv[1], sys.argv[2]
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with mesh:
    spec = steps_lib.build(arch, shape, mesh)
    compiled = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                       out_shardings=spec.out_shardings,
                       donate_argnums=spec.donate_argnums
                       ).lower(*spec.args).compile()
mem = compiled.memory_analysis()
res = hlo_analysis.analyze(compiled.as_text())
print(json.dumps({
    "temp": mem.temp_size_in_bytes,
    "flops": res["flops"],
    "coll": res["collectives"]["total_bytes"],
}))
"""


def _run_script(script, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", script, *args],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _run(arch, shape):
    return _run_script(_SCRIPT, arch, shape)


# one representative per family x step kind keeps CI time sane; the full
# 10x4 sweep runs via `python -m repro.launch.dryrun --all` (EXPERIMENTS.md)
@pytest.mark.parametrize("arch,shape", [
    ("granite-moe-3b-a800m", "decode_32k"),   # MoE + ring-free decode
    ("mamba2-370m", "train_4k"),              # SSM train (SSD scan + bwd)
    ("seamless-m4t-medium", "decode_32k"),    # enc-dec cross-attn decode
    ("yi-9b", "prefill_32k"),                 # dense GQA blockwise prefill
])
def test_lower_compile_small_mesh(arch, shape):
    res = _run(arch, shape)
    assert res["flops"] > 0
    assert res["temp"] > 0


_SERVE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import repro.configs as config_lib
from repro.core import policies
from repro.models import common, dit
from repro.serving.engine import DiffusionEngine, DiffusionRequest
from repro.sharding import partitioning

SIZE = 8
assert jax.device_count() == 8
cfg = config_lib.reduced(config_lib.get_config("dit-small"))
params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
full_fn, from_crf_fn = dit.denoiser(cfg)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
eng = DiffusionEngine(full_fn, from_crf_fn, params,
                      (SIZE, SIZE, cfg.in_channels),
                      (16, cfg.d_model),
                      policies.FreqCaPolicy(interval=3),
                      n_steps=6, max_batch=4, mesh=mesh)
assert eng.group_policies and eng.scheduler.group_policies

# sharded batch placement: a full bucket splits over the 4-way data
# axis and replicates over the 2-way model axis -> 8 lane-1 shards
x = eng._place(jnp.zeros((4, SIZE, SIZE, cfg.in_channels)))
want = partitioning.batch_spec(mesh, 4, x.ndim)
assert x.sharding.is_equivalent_to(want, x.ndim), (x.sharding, want)
shards = list(x.addressable_shards)
assert len(shards) == 8
assert all(s.data.shape == (1, SIZE, SIZE, cfg.in_channels)
           for s in shards)

# end-to-end grouped serving: alternating default/fora requests fill
# two compatibility groups -> two policy-pure sharded bucket-4 cuts
fora = policies.ForaPolicy(interval=2)
for i in range(8):
    eng.submit(DiffusionRequest(request_id=i, seed=i,
                                policy=fora if i % 2 else None), now=0.0)
outs = eng.serve_until_drained()
s = eng.metrics.summary()
assert sorted(o.request_id for o in outs) == list(range(8))
assert all(jnp.isfinite(o.latents).all() for o in outs)
assert all(o.latents.shape == (SIZE, SIZE, cfg.in_channels) for o in outs)
per_group = s["per_group"]
assert len(per_group) == 2, per_group
assert all(g["requests"] == 4 and g["batches"] == 1
           for g in per_group.values()), per_group
print(json.dumps({
    "devices": jax.device_count(),
    "placement_shards": len(shards),
    "served": len(outs),
    "groups": s["policy_groups"],
    "batches": s["batches"],
    "skip_compute_fraction": s["skip_compute_fraction"],
}))
"""


def test_grouped_serving_on_8way_mesh():
    """ROADMAP multi-host item: the bucketed engine serves a grouped
    mixed-policy stream end to end on a real 8-device mesh, with the
    batch placed over the data axis (asserted shard by shard in the
    subprocess)."""
    res = _run_script(_SERVE_SCRIPT)
    assert res["devices"] == 8
    assert res["placement_shards"] == 8
    assert res["served"] == 8
    assert res["groups"] == 2 and res["batches"] == 2
    assert 0.0 < res["skip_compute_fraction"] < 1.0
