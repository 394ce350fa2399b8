"""Runs another reader for a program that holds a SHARE of its experts:
the pairs it computes are the ones that fell on held experts
(`moe_held_pairs`), not all the router's (`moe_assignments`), and
`moe_experts_touched` already counts held experts alone. This hands the
readers written for a program that holds every expert
(`expert_roofline`) the same observations with the pairs that were
computed in the place of the pairs that were routed, in the traced
seconds' own counters (`obs["traced"]`) as in the window's.

params: reader (the module under `readers/`), params (its own).
`None` where the program counts no held pairs.
"""

import importlib


def read(ctx, reader, params=None):
    obs = ctx["obs"]
    if "moe_held_pairs" not in obs:
        return None
    held = dict(obs, moe_assignments=obs["moe_held_pairs"])
    traced = obs.get("traced") or {}
    if "moe_held_pairs" in traced:    # the traced seconds' own counters
        held["traced"] = dict(traced,
                              moe_assignments=traced["moe_held_pairs"])
    inner = importlib.import_module("readers." + reader)
    return inner.read(dict(ctx, obs=held), **(params or {}))
