"""A cell's files, found by the names in ``BENCHMARK.json``.

Nothing here names a particular cell, configuration, traffic mix or
metric: a new one is a new file.

  BENCHMARK.json                    cells (workloads) and metrics
  bench/configs/<config>.json       model sizes, engine settings, family
  bench/traffic/<traffic>.json      load parameters (see loadgen.py)
  bench/limits/<cell>.json          the limits ``correct`` is held to
  bench/metrics/<metric>.py         ``read(run)`` for each metric
  bench/programs/<family>.py        the program's side of a family
  bench/references/<family>.py      the plain reference of the family
  bench/peaks.json                  chip peaks by ``device_kind``

A family is the ``family`` of a configuration file.  Its program file
defines:

  denoiser(model, name)             (full_fn, from_crf_fn)
  weights(model, name, seed, device)  the weights, on the device
  policy(spec)                      the cache policy of a traffic file
  request(cell, arrival, lat)       the served ``DiffusionRequest`` of an
                                    arrival, its conditioning drawn from
                                    a fold of the arrival's seed
  engine(cell, full_fn, from_crf_fn, params, lat, crf, pol)
                                    the ``DiffusionEngine``
  attention_tokens(model, s)        tokens an attention call runs over,
                                    for ``s`` image tokens
  flash_calls(model)                flash calls of one full lane-step
  forward_flops(model, s)           operations of one forward of one image

and its reference file:

  make_weights(model, seed)         the reference's own weights
  Reference(model, policy, n_steps, lat, quant=None)
                                    ``.sample(weights, **inputs)`` ->
                                    (latents, number of full steps)
  inputs(ref, cell, arrival)        the keyword inputs of ``sample`` for
                                    an arrival, drawn as ``request``
                                    draws them
  rel_err(got, want)                the compared relative error
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    traffic: dict         # the traffic file
    limits: dict          # the limits file
    metrics: List[dict]   # BENCHMARK.json entries reported by this cell

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def engine(self) -> dict:
        return self.config["engine"]

    @property
    def policy(self) -> dict:
        return self.traffic["policy"]

    @property
    def family(self) -> str:
        return self.config["family"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, trace: bool, root: Path = ROOT) -> Cell:
    """The cell ``name`` with the metrics a ``--trace`` run reports:
    end-to-end metrics without a trace, per-layer metrics with one."""
    bench = _json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    metrics = bench["per_layer" if trace else "end_to_end"]
    return Cell(name=name, chips=w["chips"],
                config=_json(root / conf["file"]),
                traffic=_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=_json(root / "bench" / "limits" / f"{name}.json"),
                metrics=[m for m in metrics if _applies(m, name)])


def peaks() -> dict:
    return _json(BENCH / "peaks.json")


def reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py")


def program(family: str):
    return load_module(BENCH / "programs" / f"{family}.py")


def reference(family: str):
    return load_module(BENCH / "references" / f"{family}.py")
