"""Which routing counters the expert roofline prices its traced steps at,
on the CPU, on observations made up here (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The traced seconds' own counters (`obs["traced"]`) where the kind read
them, the measured window's mean where it did not; through
`held_experts` the pairs that fell on held experts, of the traced
seconds too; through `expert_layers` the layers that have experts; the
SAME share whether the trace holds the products under XLA's `ragged-dot`,
under the reserved `expert_grouped_matmul`, or split between them (PR
52). And `serve_step_mfu` (`readers/step_mfu.py`), the whole step's
share of the chip's peak, against a hand count on a made-up window of
each of the eight families.
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
             params=dict(match=["ragged-dot", "expert_grouped_matmul"],
                         exclude=["metadata"]))


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


# -- whatever computes the experts' products (PR 52) ------------------------

NEMOTRON = dict(n_layers=14, expert_layers=4, state_layers=6, ssm_heads=64,
                d_model=4096, d_ff=4096)
SPLITS = {
    "xla": {"ragged-dot-none": KERNEL_S},
    "own": {"expert_grouped_matmul": KERNEL_S},
    "split": {"ragged-dot-none": 0.25 * KERNEL_S,
              "expert_grouped_matmul": 0.5 * KERNEL_S,
              "jit_expert_grouped_matmul_down": 0.25 * KERNEL_S},
}


@pytest.mark.parametrize("family", sorted(SPLITS))
@pytest.mark.parametrize("spec", [
    PLAIN,
    dict(reader="expert_layers", params=dict(PLAIN)),
    dict(reader="held_experts", params=dict(PLAIN)),
    dict(reader="nemotron3_stream",
         params=dict(PLAIN["params"], which="experts")),
], ids=["rollout", "expert_layers", "cmda", "nemotron3"])
def test_the_share_is_the_works_not_the_names(spec, family):
    """The four expert rooflines read the same share with the seconds
    under `ragged-dot-none`, under `expert_grouped_matmul`, and split
    between them; `metadata` families and the other fusions stay out."""
    def counts(steps, touched):
        return dict(_counts(steps, touched), moe_held_pairs=6 * steps * 4,
                    state_slot_steps=1, decode_steps=steps)

    def read(op_seconds):
        ctx = _ctx(counts(3000, 4.0), counts(200, 5.0),
                   **(NEMOTRON if spec["reader"] == "nemotron3_stream"
                      else {}))
        ctx["reduced"]["op_seconds"] = dict(
            op_seconds, **{"ragged-dot-metadata": 9.0, "fusion": 9.0,
                           "expert_grouped_matmul-metadata": 9.0})
        return common.read_metrics({"m": dict(spec, unit="%")}, ctx)

    want = read(SPLITS["xla"])["m"]["value"]
    two_matrices = spec["reader"] == "nemotron3_stream"
    assert want == pytest.approx(
        _share(5.0) * (2 / 3 if two_matrices else 1), rel=1e-9)
    assert read(SPLITS[family])["m"]["value"] == pytest.approx(want,
                                                               rel=1e-12)
    assert read({"fusion.1": 1.0}) == {}


def test_a_single_string_is_a_list_of_one():
    from readers import expert_roofline
    ops = {"ragged-dot-none": 1.0, "ragged-dot-metadata": 1.0, "fusion": 1.0}
    assert expert_roofline.families(ops, "ragged-dot", ["metadata"]) \
        == expert_roofline.families(ops, ["ragged-dot"], ["metadata"]) \
        == ["ragged-dot-none"]


# -- serve_step_mfu: the whole step's share ---------------------------------

ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
PEAK_FLOPS = 197e12
STEPS, WINDOW_S, PREFILLS, BLOCK = 100, 10.0, 3, 16
F32 = 4.0


def _cell_model(name):
    import importlib
    cell = common.Cell(MANIFEST, name)
    harness = cell.config.get("harness")
    if harness:
        sz = importlib.import_module(
            "kinds." + harness["mapping"]).sizes(cell.config)
        return cell, dict(sz, **sz["block"])
    from kinds import _model
    return cell, _model.sizes(cell.config)


def _window(name, rows, prompt, touched=None, **more):
    """A made-up window of `STEPS` steps with every slot live at `rows`
    cache rows, `touched` experts a layer and step, `PREFILLS`
    admissions of `prompt` tokens: (ctx, model, slots)."""
    cell, model = _cell_model(name)
    slots = int(cell.config["serving"]["slots"])
    live = STEPS * slots
    obs = dict(model=model, block_size=BLOCK, window_s=WINDOW_S,
               decode_steps=STEPS, slots_used_sum=live,
               slots_capacity_sum=live, prefills=PREFILLS,
               prefill_tokens=PREFILLS * prompt,
               paged_live_pages=live * -(-rows // BLOCK))
    if touched is not None:
        layers = model.get("expert_layers",
                           model["n_layers"] - model.get("dense_layers", 0))
        obs.update(moe_layer_steps=STEPS * layers,
                   moe_experts_touched=touched * STEPS * layers,
                   moe_assignments=live * model["experts_per_tok"] * layers)
    obs.update({k: v(live) if callable(v) else v for k, v in more.items()})
    ctx = dict(obs=obs, cell=cell,
               device=dict(platform="tpu", kind="TPU v5 lite"))
    return ctx, model, slots


def _mfu(ctx):
    spec = ctx["cell"].per_layer["serve_step_mfu"]
    return common.read_metrics({"m": spec}, ctx).get("m", {}).get("value")


def _hand(steps_bytes, steps_flops, always, head, prompt, routed=0.0):
    """100 x (steps' least + admissions' least) / window from bytes and
    weight counts worked out by hand: `always` and `head` in weights."""
    steps = max(steps_bytes / HBM, steps_flops / PEAK_FLOPS)
    admit = max(PREFILLS * F32 * (always + routed) / HBM,
                2.0 * PREFILLS * (prompt * (always - head + routed) + head)
                / PEAK_FLOPS)
    return 100.0 * (steps + admit) / WINDOW_S


# rows a slot 1,000 -> 63 pages: 62 whole and the last at ONE row
ROWS = 62 * BLOCK + 1


def _gpt2():
    ctx, m, slots = _window("cgpt1p3b_serve_rollout", 1000, 144)
    layer = 4 * 2048 * 2048 + 2 * 2048 * 8192 + 4 * 2048 + 8192 + 2048 \
        + 4 * 2048
    head = 2048 * 50257 + 50257 + 2 * 2048
    always = 24 * layer + head
    cache = slots * ROWS * 24 * 2 * 2048
    return ctx, _hand(STEPS * F32 * (always + cache),
                      2.0 * STEPS * slots * always, always, head, 144)


def _olmoe():
    ctx, m, slots = _window("olmoe1b7b_serve_rollout", 1000, 544,
                            touched=56)
    expert = 3 * 2048 * 1024
    layer = 4 * 2048 * 2048 + 2048 * 64 + 4 * 2048
    head = 2048 * 50304 + 2048
    always = 5 * layer + head
    cache = slots * ROWS * 5 * 2 * 2048
    flops = 2.0 * STEPS * slots * (always + 8 * 5 * expert)
    return ctx, _hand(
        STEPS * F32 * (always + 5 * 56 * expert + cache), flops, always,
        head, 544, routed=8 * 5 * expert)


def _kanana():
    ctx, m, slots = _window("kanana2_30b_serve_rollout_6k", 1000, 4224,
                            touched=59)
    expert = 3 * 2048 * 768
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 \
        + 32 * 128 * 2048 + 2 * 2048 + 512
    sparse = 2048 * 128 + 128 + 3 * 2048 * 1536
    head = 2048 * 128256 + 2048
    always = 5 * attention + 3 * 2048 * 6144 + 4 * sparse + head
    cache = slots * ROWS * 5 * 576        # 576 floats, not the 640 stored
    flops = 2.0 * STEPS * slots * (always + 6 * 4 * expert)
    return ctx, _hand(
        STEPS * F32 * (always + 4 * 59 * expert + cache), flops, always,
        head, 4224, routed=6 * 4 * expert)


def _keye():
    ctx, m, slots = _window(
        "keye2_30b_serve_rollout_6k", 5000, 4608, touched=82,
        sparse_live_rows=lambda live: live * 5000,
        sparse_selected_rows=lambda live: live * 2048)
    expert = 3 * 2048 * 768
    layer = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128 \
        + 2048 * (16 * 64 + 64 + 16) + 2 * 2048 + 2 * 128 + 2 * 64 \
        + 2048 * 128
    head = 2048 * 151936 + 2048
    always = 4 * layer + head
    cache = slots * 4 * (5000 * 64 + 2048 * 2 * 4 * 128)
    flops = 2.0 * STEPS * slots * (always + 8 * 4 * expert)
    return ctx, _hand(
        STEPS * F32 * (always + 4 * 82 * expert + cache), flops, always,
        head, 4608, routed=8 * 4 * expert)


def _cmda():
    ctx, m, slots = _window(
        "cmdaplus_serve_rollout_10k", 7000 - 7000 % BLOCK + 1, 4992,
        touched=4,
        window_rows_read=lambda live: live * 3 * 4096,
        moe_held_pairs=lambda live: live // 2 * 4)
    expert = 3 * 4096 * 4096
    layer = 2 * 4096 * 128 * 128 + 2 * 4096 * 8 * 128 + 4096 * 128 \
        + 3 * 4096 * 16384 + 4096
    head = 4096 * 32768 + 4096
    always = 4 * layer + head
    rows = 7000 - 7000 % BLOCK + 1
    cache = slots * (3 * 4096 + rows) * 2 * 8 * 128
    # the pairs that fell on HELD experts, not all the router's
    flops = 2.0 * STEPS * (slots * always + slots // 2 * 4 * expert)
    return ctx, _hand(
        STEPS * F32 * (always + 4 * 4 * expert + cache), flops, always,
        head, 4992)


def _lfm2():
    ctx, m, slots = _window(
        "lfm2_24b_serve_rollout_6k_s64", 1000, 4224, touched=56,
        state_slot_steps=lambda live: 5 * live)
    expert = 3 * 2048 * 1536
    conv = 4 * 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 32 * 64 + 2 * 2048 * 8 * 64 + 2 * 64
    head = 2048 * 65536 + 2048
    always = 5 * conv + attention + 6 * 2 * 2048 \
        + 2 * 3 * 2048 * 11776 + 4 * (2048 * 64 + 64) + head
    cache = slots * ROWS * 2 * 8 * 64
    states = slots * 5 * 2 * 2 * 2048
    flops = 2.0 * STEPS * slots * (always + 4 * 4 * expert)
    return ctx, _hand(
        STEPS * F32 * (always + 4 * 56 * expert + cache + states), flops,
        always, head, 4224, routed=4 * 4 * expert)


def _phi4flash():
    ctx, m, slots = _window(
        "phi4flash_serve_rollout_reason_s64", 1756, 560,
        pool_rows_read_writer=lambda live: live * 1756,
        pool_rows_read_readers=lambda live: 3 * live * 1756,
        window_rows_read=lambda live: 4 * live * 512,
        state_slot_steps=lambda live: 5 * live)
    d, f = 2560, 10240
    q = d * 40 * 64 + 40 * 64
    kv = d * 20 * 64 + 20 * 64
    out = 40 * 64 * d + d
    small = 6 * 64
    scan = d * 2 * 5120 + 4 * 5120 + 5120 + 5120 * (160 + 32) \
        + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * d
    head = d * 200064 + 2 * d
    always = 5 * scan + 5 * (q + 2 * kv + out + small) \
        + 3 * (q + out + small) + 3 * 2 * d * 5120 \
        + 16 * (3 * d * f + 4 * d) + head
    cache = slots * (4 * 1756 + 4 * 512) * 2 * 20 * 64
    states = slots * 5 * 2 * 5120 * (16 + 3)
    return ctx, _hand(STEPS * F32 * (always + cache + states),
                      2.0 * STEPS * slots * always, always, head, 560)


def _nemotron3(d_ff_stored=None, d_model_stored=None):
    ctx, m, slots = _window(
        "nemotron3_nano_serve_rollout_reason_s128", 1000, 560, touched=29,
        state_slot_steps=lambda live: 6 * live,
        moe_held_pairs=lambda live: live * 6 * 6 // 4)
    d = 2688
    expert = 2 * d * 1856             # PUBLISHED: 1,856 x 2,688, two matrices
    width = 4096 + 2 * 8 * 128
    mamba = d * (4096 + width + 64) + 5 * width + 3 * 64 + 4096 + 4096 * d
    attention = 2 * d * 32 * 128 + 2 * d * 2 * 128
    experts = d * 128 + 128 + 2 * d * 3712
    head = d * 32768 + d
    always = 6 * mamba + 2 * attention + 6 * experts + 14 * d + head
    cache = slots * ROWS * 2 * 2 * 2 * 128
    states = slots * 6 * 2 * (4096 * 128 + 3 * width)
    flops = 2.0 * STEPS * slots * (always + 6 * 6 / 4 * expert)
    return ctx, _hand(
        STEPS * F32 * (always + 6 * 29 * expert + cache + states), flops,
        always, head, 560)


FAMILIES = dict(gpt2=_gpt2, olmoe=_olmoe, kanana=_kanana, keye=_keye,
                cmda=_cmda, lfm2=_lfm2, phi4flash=_phi4flash,
                nemotron3=_nemotron3)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_whole_steps_share_is_the_hand_count(family):
    """`serve_step_mfu` on a made-up window of each family: the least
    bytes of its steps (weights by the touched experts, live rows with a
    slot's last page at one row, live slots' states) and its admissions,
    counted by hand at the PUBLISHED widths."""
    ctx, want = FAMILIES[family]()
    got = _mfu(ctx)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0 < got < 100
    assert len(ctx["cell"].per_layer) >= 24


def test_no_share_without_a_step_a_counter_or_a_chip():
    ctx, _ = _nemotron3()
    ctx["obs"]["decode_steps"] = 0
    assert _mfu(ctx) is None
    ctx, _ = _nemotron3()
    del ctx["obs"]["paged_live_pages"]    # a kind that does not read it
    assert _mfu(ctx) is None
    ctx, _ = _nemotron3()
    ctx["device"]["platform"] = "cpu"
    assert _mfu(ctx) is None


def test_the_share_falls_when_the_stored_widths_grow():
    """The numerator is priced at the PUBLISHED widths whatever the
    program stores. A program that reads what it STORES at the HBM's
    rate makes fewer steps in the window the wider it stores its
    experts; its share falls, and stored as published it is 100."""
    def window_of(f_stored, d_stored):
        """The Nemotron cell's steps by a program that streams every
        touched expert's two matrices as stored, and the rest, at the
        chip's rate for the whole window."""
        ctx, _ = _nemotron3()
        obs, model = ctx["obs"], ctx["obs"]["model"]
        import flops_nemotron3
        parts = flops_nemotron3.decode_least_bytes(
            dict(obs, live_rows=obs["slots_used_sum"] * ROWS), **model)
        published = sum(parts.values()) / STEPS
        stored = published + 6 * 29 * F32 * 2 * (
            f_stored * d_stored - 1856 * 2688)
        obs["window_s"] = STEPS * stored / HBM
        obs["prefills"] = obs["prefill_tokens"] = 0
        # what the program stores is in its observations too, and is
        # never what the reader prices
        obs["model"] = dict(model, d_ff_stored=f_stored,
                            d_model_stored=d_stored)
        return _mfu(ctx)

    as_published = window_of(1856, 2688)
    tiles_of_256 = window_of(2048, 2688)           # PR 51's storage
    whole_tiles = window_of(2048, 3072)            # and the model width
    assert as_published == pytest.approx(100.0, rel=1e-9)
    assert as_published > tiles_of_256 > whole_tiles > 85
    # 12.76 GB a step as published; 192 more columns of 29 touched
    # experts' two matrices in 6 layers are 0.72 GB more
    extra = 6 * 29 * F32 * 2 * 192 * 2688
    assert tiles_of_256 == pytest.approx(
        100 * 12756341760 / (12756341760 + extra), rel=1e-9)
