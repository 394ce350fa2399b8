"""Runs another reader for a program that holds a SHARE of its experts:
the pairs it computes are the ones that fell on held experts
(`moe_held_pairs`), not all the router's (`moe_assignments`), and
`moe_experts_touched` already counts held experts alone. This hands the
readers written for a program that holds every expert
(`expert_roofline`) the same observations with the pairs that were
computed in the place of the pairs that were routed.

params: reader (the module under `readers/`), params (its own).
`None` where the program counts no held pairs.
"""

import importlib


def read(ctx, reader, params=None):
    obs = ctx["obs"]
    if "moe_held_pairs" not in obs:
        return None
    inner = importlib.import_module("readers." + reader)
    return inner.read(
        dict(ctx, obs=dict(obs, moe_assignments=obs["moe_held_pairs"])),
        **(params or {}))
