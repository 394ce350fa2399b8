"""Which routing counters the expert roofline prices its traced steps at,
on the CPU, on observations made up here (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The traced seconds' own counters (`obs["traced"]`) where the kind read
them, the measured window's mean where it did not; through
`held_experts` the pairs that fell on held experts, of the traced
seconds too; through `expert_layers` the layers that have experts.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE]

import common  # noqa: E402

MODEL = dict(n_layers=4, dense_layers=0, d_model=4096, d_ff=4096)
EXPERT_BYTES = 4 * 3.0 * 4096 * 4096
KERNEL_S = 1.0
STEPS = 200
HBM = 819e9


def _ctx(window, traced=None, **model):
    obs = dict(window, model=dict(MODEL, **model),
               kernel=dict(calls=STEPS))
    if traced is not None:
        obs["traced"] = traced
    return dict(obs=obs, device=dict(platform="tpu", kind="TPU v5 lite"),
                reduced=dict(op_seconds={"ragged-dot-none": KERNEL_S,
                                         "ragged-dot-metadata": 9.0,
                                         "fusion": 9.0}))


def _counts(steps, touched_a_layer_step, layers=4, **more):
    return dict(moe_layer_steps=steps * layers, moe_assignments=
                6 * steps * layers, moe_experts_touched=int(
                    touched_a_layer_step * steps * layers), **more)


def _share(touched_a_layer_step, layers=4):
    """With a few rows a step the weights' bytes bound the experts."""
    return 100.0 * (STEPS * layers * touched_a_layer_step * EXPERT_BYTES
                    / HBM) / KERNEL_S


def _read(spec, ctx):
    return common.read_metrics({"m": dict(spec, unit="%")}, ctx)["m"]["value"]


PLAIN = dict(reader="expert_roofline",
             params=dict(match=["ragged-dot"], exclude=["metadata"]))


@pytest.mark.parametrize("traced, touched", [
    (None, 4.0),                          # no traced counters: the window's
    (_counts(210, 5.0), 5.0),             # the traced seconds' own
    (_counts(190, 3.5, prefills=1), 3.5),
    ({}, 4.0),                            # a kind that read none
])
def test_traced_steps_are_priced_at_their_own_counters(traced, touched):
    got = _read(PLAIN, _ctx(_counts(3000, 4.0), traced))
    assert got == pytest.approx(_share(touched), rel=1e-9)


def test_held_pairs_of_the_traced_seconds():
    """So many rows an expert that the products bound it (over 481 pairs
    a touched expert on this chip): the share then shows WHICH pairs
    were priced, the held ones of the traced seconds."""
    def counts(steps, held_a_layer_step):
        return dict(_counts(steps, 4.0), moe_assignments=10 ** 9,
                    moe_held_pairs=held_a_layer_step * steps * 4)
    spec = dict(reader="held_experts", params=dict(PLAIN))
    got = _read(spec, _ctx(counts(3000, 3000), counts(200, 4000)))
    flops_of = 2.0 * (STEPS * 4 * 4000) * EXPERT_BYTES / 4
    assert got == pytest.approx(100.0 * flops_of / 197e12 / KERNEL_S,
                                rel=1e-9)


def test_expert_layers_counts_the_layers_with_experts():
    window = _counts(3000, 4.0, layers=3)
    traced = _counts(200, 5.0, layers=3)
    spec = dict(reader="expert_layers", params=dict(PLAIN))
    got = _read(spec, _ctx(window, traced, dense_layers=1))
    assert got == pytest.approx(_share(5.0, layers=3), rel=1e-9)


def test_nothing_to_read_off_the_chip_or_without_the_kernel():
    ctx = _ctx(_counts(3000, 4.0), _counts(200, 5.0))
    ctx["device"]["platform"] = "cpu"
    assert common.read_metrics({"m": dict(PLAIN, unit="%")}, ctx) == {}
    ctx = _ctx(_counts(3000, 4.0), _counts(200, 5.0))
    ctx["reduced"]["op_seconds"] = {"fusion": 1.0}
    assert common.read_metrics({"m": dict(PLAIN, unit="%")}, ctx) == {}
