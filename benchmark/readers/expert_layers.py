"""Runs another reader for a model whose leading layers are dense: the
routing counters count the layers that HAVE experts, and the readers
written for a model of expert layers only (`expert_roofline`) divide
`moe_layer_steps` by the model's `n_layers` to get steps. This hands
them the same observations with `n_layers` set to the expert layers.

params: reader (the module under `readers/`), params (its own).
`None` where there is no model to read.
"""

import importlib


def read(ctx, reader, params=None):
    obs = ctx["obs"]
    model = obs.get("model")
    if not model:
        return None
    moe = dict(model, n_layers=model["n_layers"]
               - model.get("dense_layers", 0))
    inner = importlib.import_module("readers." + reader)
    return inner.read(dict(ctx, obs=dict(obs, model=moe)), **(params or {}))
