"""The program's side of a ``dit`` configuration: its denoiser pair,
seeded weights on the device, the cache policy a traffic file asks for,
the served request of an arrival, the engine, and the counts the metric
readers take from the family.

The served path carries no text: the single-stream blocks alone run
over the image tokens, and an edit's reference latents are the only
conditioning a request carries."""
from __future__ import annotations

import dataclasses

from bench import loadgen, work


def config(model: dict, name: str):
    from repro.configs.base import DiTConfig
    fields = {f.name for f in dataclasses.fields(DiTConfig)}
    return DiTConfig(arch_id=name,
                     **{k: v for k, v in model.items() if k in fields})


def denoiser(model: dict, name: str):
    from repro.models import dit
    return dit.denoiser(config(model, name))


def weights(model: dict, name: str, seed: int, device):
    from repro.models import dit
    return dit.random_params(config(model, name), seed, device)


def policy(spec: dict):
    """The registered policy class whose ``name`` is ``spec["name"]``,
    built from the rest of the spec."""
    from repro.core import policies
    from repro.core.policies import base

    policies.available()            # registers the built-in policies
    todo, found = [base.Policy], {}
    while todo:
        cls = todo.pop()
        found.setdefault(cls.name, cls)
        todo.extend(cls.__subclasses__())
    args = {k: v for k, v in spec.items() if k != "name"}
    return found[spec["name"]](**args)


def request(cell, a: loadgen.Arrival, lat: tuple):
    """The served request of arrival ``a``: an edit starts from the
    reference latents drawn from a fold of its seed."""
    from repro.serving.scheduler import DiffusionRequest
    if a.edit:
        return DiffusionRequest(
            request_id=a.index, seed=a.seed,
            init_latents=loadgen.edit_reference(a, lat),
            edit_strength=cell.traffic["edit_strength"])
    return DiffusionRequest(request_id=a.index, seed=a.seed)


def engine(cell, full_fn, from_crf_fn, params, lat: tuple, crf: tuple,
           pol):
    """The engine the window drives, with the configuration's engine
    settings and the cache policy ``pol``."""
    from repro.serving.engine import DiffusionEngine
    eng = cell.engine
    return DiffusionEngine(full_fn, from_crf_fn, params, lat, crf, pol,
                           n_steps=eng["n_steps"],
                           max_batch=eng["max_batch"],
                           max_wait_s=eng["max_wait_s"])


def attention_tokens(model: dict, s: int) -> int:
    """Tokens each attention call runs over: the ``s`` image tokens."""
    return s


def flash_calls(model: dict) -> int:
    """Flash calls of one full lane-step: one per single-stream block."""
    return model["n_layers"]


def forward_flops(model: dict, s: int) -> float:
    """Operations of one forward of one image over ``s`` image tokens."""
    return work.forward_flops(model, s)
