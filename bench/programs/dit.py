"""The program's side of a ``dit`` configuration: its denoiser pair,
seeded weights on the device, and the cache policy a traffic file asks
for."""
from __future__ import annotations

import dataclasses


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
