"""The cell `mellum2_12b_train_seq8k` and its entries in the manifest, on
the CPU, with numpy and this directory's loader and arithmetic alone, and
then (from `tiny_cell` down) the comparison that decides the cell's
`correct`, THROUGH the kind at cut widths with one fault planted at a
time: a sound first call is correct; a state left unchanged, a wave of
the share's forward or of its backward that does not run, and gradients
of another size are not:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The traffic file's parameters as ISSUE 62 gave them, the catalog's keys
(every one but the three in `reduced`), the cut's arithmetic at 16 bytes a
parameter, the cell's own seven entries under `.mellum2` as the
manifest's last, the seven lists of the train cell's metrics that name it
last, and the operations `flops_mellum2.py` counts (the band's visible
pairs, the forward's 0.50 G a token). Beside the serve cells' files and
`test_manifest.py`.
"""

import copy
import json
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE]

import common  # noqa: E402
import flops_mellum2  # noqa: E402
import workload  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, SUFFIX = ("mellum2_12b_train_seq8k",
                        "mellum2-12b-a2.5b-train-1chip", ".mellum2")
OWN = ("flash_fwd_roofline", "flash_bwd_roofline", "flash_win_fwd_roofline",
       "flash_win_bwd_roofline", "moe_expert_roofline",
       "moe_held_pair_share", "moe_largest_group_share")
SHARED = ("exe_host_ms_per_step", "compiles_in_window.train",
          "device_idle_share.train", "train_mfu",
          "phase_overrun_share.train", "gc_pause_share.train")
REDUCED = {"num_hidden_layers": (28, 4), "num_experts": (64, 16),
           "vocab_size": (98304, 24576)}


@pytest.fixture(scope="module")
def mellum2_cell():
    return common.Cell(MANIFEST, CELL)


def _file(name):
    return common.load_json(os.path.join(HERE, "layer_metrics",
                                         name + ".json"))


def test_the_file_carries_the_issues_parameters(mellum2_cell):
    cell = mellum2_cell
    tr = cell.traffic
    assert (tr["kind"], tr["seq_len"], tr["sequences_per_step"],
            tr["steps_per_call"]) == ("train_stream_mapped", 8192, 1, 8)
    assert cell.chips == 1 and cell.entry["traffic"] == "lm_stream_8k_seq8k"
    assert cell.entry["config"] == CONFIG
    assert set(cell.end_to_end) == {"train_tokens_per_s", "setup_s"}
    # ids from --seed, under the held rows of the vocabulary
    src, tgt = next(workload.token_windows(2 ** 31 + 7, 24576, 2, 1, 64))
    assert src.shape == (2, 1, 64) and tgt.shape == (2, 1, 64, 1)
    assert 0 <= src.min() and src.max() < 24576
    assert (src[..., 1:] == tgt[..., :-1, 0]).all()


def test_the_configuration_is_the_catalogs_but_for_its_three_cuts(
        mellum2_cell):
    cell = mellum2_cell
    cfg = cell.config
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == row["source_url"]
    manifest = common.load_json(MANIFEST)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) \
        == sorted(REDUCED)
    for key, value in row["config"].items():
        if key in REDUCED:
            assert (value, cfg[key]) == REDUCED[key], key
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key       # nested groups whole
    assert cfg["published"]["held_experts"] == {"first": 0, "count": 16}
    assert cfg["harness"] == {"mapping": "_model_mellum2",
                              "reference": "reference_mellum2"}
    assert cfg["train"]["amp_dtype"] == "bfloat16" \
        and cfg["train"]["remat"] is False
    # no width is cut, and the floors hold: a whole period of four, at
    # least 8 experts, at least an eighth of the vocabulary
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 98304


def test_the_cuts_arithmetic(mellum2_cell):
    """16 bytes a parameter: 595.2 M parameters, 9.52 GB of the chip's 16;
    a second period or 32 experts a chip would not fit."""
    cell = mellum2_cell
    c = cell.config
    d, hd = c["hidden_size"], c["head_dim"]
    outside = d * hd * (2 * c["num_attention_heads"]
                        + 2 * c["num_key_value_heads"]) \
        + d * c["published"]["num_experts"] + 2 * d
    expert = 3 * d * c["moe_intermediate_size"]
    assert (outside, expert) == (21_385_728, 6_193_152)
    whole = 28 * (outside + 64 * expert) + 2 * 98304 * d + d
    active = 28 * (outside + 8 * expert) + 2 * 98304 * d + d
    assert round(whole / 1e9, 2) == 12.15 and round(active / 1e9, 2) == 2.44
    layer = outside + c["num_experts"] * expert
    head = 2 * c["vocab_size"] * d + d
    held = c["num_hidden_layers"] * layer + head
    assert (layer, head, held) == (120_476_160, 113_248_512, 595_153_152)
    assert round(16 * held / 1e9, 2) == 9.52
    assert 16 * (held + 4 * layer) / 1e9 > 16.9         # a second period
    assert 16 * (held + 4 * 16 * expert) / 1e9 > 15.8   # 32 experts a chip
    assert str(held // 1000 * 1000)[:3] in c["reduced_how"]["arithmetic"] \
        .replace(",", "")


def test_the_cell_lists_its_own_metrics(mellum2_cell):
    """What is this configuration's own stays under its suffix, listing
    this cell alone and LAST in the manifest (appended: nothing put in
    the middle); the train cell's common metrics name it last."""
    cell = mellum2_cell
    manifest = common.load_json(MANIFEST)
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    suffixed = [e for e in manifest["per_layer"]
                if e["name"].endswith(SUFFIX)]
    assert [e["name"] for e in suffixed] == [b + SUFFIX for b in OWN]
    assert manifest["per_layer"][-len(OWN):] == suffixed
    assert all(e["workloads"] == [CELL] for e in suffixed)
    for base in OWN:
        entry, spec = by_name[base + SUFFIX], _file(base + SUFFIX)
        assert entry["moves"] == spec["moves"] == "train_tokens_per_s"
        assert (entry["unit"], entry["layer"]) \
            == (spec["unit"], spec["layer"]) and entry["unit"] == "%"
    for base in OWN[:5]:
        assert by_name[base + SUFFIX]["source"] == "device_trace"
        assert by_name[base + SUFFIX]["layer"] == "kernels"
        assert _file(base + SUFFIX)["params"]["module"] == "flops_mellum2"
    for base in OWN[5:]:
        assert by_name[base + SUFFIX]["source"] == "program_counter"
        assert _file(base + SUFFIX)["reader"] == "ratio"
    # the four flash shares tell a windowed call from a full one by name,
    # forward from backward
    match = {b: (_file(b + SUFFIX)["params"]["match"],
                 _file(b + SUFFIX)["params"].get("exclude", []))
             for b in OWN[:4]}
    names = ("scaled_dot_product_attention",
             "transpose_scaled_dot_product_attention",
             "windowed_dot_product_attention",
             "transpose_windowed_dot_product_attention")
    for base, own in zip(OWN[:4], names):
        taken = [n for n in names
                 if all(m in n for m in match[base][0])
                 and not any(x in n for x in match[base][1])]
        assert taken == [own], base
    shared = [e for e in manifest["per_layer"]
              if CELL in e["workloads"] and not e["name"].endswith(SUFFIX)]
    assert sorted(e["name"] for e in shared) == sorted(SHARED)
    assert all(e["workloads"][-1] == CELL for e in shared)
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == CONFIG
    train = next(e for e in manifest["end_to_end"]
                 if e["name"] == "train_tokens_per_s")
    assert train["workloads"] == ["cgpt1p3b_train_seq2k", CELL]
    assert set(cell.per_layer) == {b + SUFFIX for b in OWN} | set(SHARED)


def test_the_operations_count_the_band_and_the_held_pairs():
    # row t reads min(t + 1, window) keys
    for seq, window in ((8, None), (8, 3), (8192, 1024), (2048, 4096)):
        want = sum(min(t + 1, window or seq) for t in range(seq))
        assert flops_mellum2.visible_pairs(seq, window) == want
    shape = dict(calls=8, batch=1, heads=32, kv_heads=4, seq_len=8192,
                 head_dim=128)
    full, _ = flops_mellum2.flash_fwd(**shape)
    band, _ = flops_mellum2.flash_fwd(window=1024, **shape)
    assert round(full / band, 2) == 4.27        # the band's saving
    assert flops_mellum2.flash_bwd(**shape)[0] == 2.5 * full
    model = dict(d_model=2304, heads=32, kv_heads=4, head_dim=128,
                 window=1024, window_layers=3, full_layers=1,
                 expert_width=896, experts=64, held=16, top_k=8,
                 vocab=24576, seq_len=8192)
    fwd = flops_mellum2.forward_flops_per_token(**model)
    assert round(fwd / 1e9, 2) == 0.50          # ISSUE 62's estimate
    assert flops_mellum2.train_flops_per_token(**model) == 3 * fwd
    # at the pairs counted, not the even routing's: twice the pairs on
    # held experts, twice the experts' operations
    even = flops_mellum2.forward_flops_per_token(
        held_pairs_per_token=2.0, **model)
    assert even == fwd
    more = flops_mellum2.forward_flops_per_token(
        held_pairs_per_token=4.0, **model)
    assert round((more - fwd) / 1e6, 1) == round(
        4 * 2 * 3 * 2 * 2304 * 896 / 1e6, 1)
    # nine products a pair
    ops, moved = flops_mellum2.expert_products(
        pairs=16384 * 32, layer_steps=32, held=16, d_model=2304,
        expert_width=896)
    assert ops == 9 * 2 * 2304 * 896 * 16384 * 32 and moved > 0


# -- the comparison that decides `correct`, through the kind ----------------

def tiny_cell(**train):
    """The cell's own configuration at cut widths (d 32, 4 / 2 heads of
    16, a window of 5, experts 2-5 of 8 held, top-3, 64 ids), 2 steps of
    64 tokens a call, in float32: on the CPU the program's float32 is the
    reference's, so a sound first call reads what the limits were set
    about and a planted fault is all that moves a reading."""
    cfg = copy.deepcopy(common.Cell(MANIFEST, CELL).config)
    cfg.update(hidden_size=32, head_dim=16, num_attention_heads=4,
               num_key_value_heads=2, moe_intermediate_size=24,
               vocab_size=64, sliding_window=5, num_experts=4,
               num_experts_per_tok=3)
    cfg["published"].update(num_experts=8,
                            held_experts={"first": 2, "count": 4})
    cfg["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=64, beta_fast=4, beta_slow=0.25)
    cfg["train"].update(amp_dtype=None, **train)
    return types.SimpleNamespace(
        name="tiny", chips=1, config=cfg,
        traffic=dict(kind="train_stream_mapped", seq_len=64,
                     sequences_per_step=1, steps_per_call=2))


def first_call(capsys, **train):
    """(`correct`, the numbers the kind's check printed) of one run."""
    pytest.importorskip("jax")
    from kinds import train_stream_mapped
    args = types.SimpleNamespace(seed=2 ** 31 + 5, seconds=0.01, trace=0,
                                 rehearse="tiny")
    capsys.readouterr()
    out = train_stream_mapped.run(tiny_cell(**train), args, {},
                                  time.perf_counter())
    read = next(json.loads(line)
                for line in capsys.readouterr().out.splitlines()
                if line.startswith('{"check"'))
    assert read["correct"] == out["correct"]
    return out["correct"], read


def test_a_sound_first_call_is_correct(capsys):
    correct, read = first_call(capsys)
    assert correct
    assert read["held_pairs"] == read["reference_held"] > 0
    assert sorted(read["update"]) == sorted(
        ["window.q", "window.k", "window.v", "full.q", "full.k", "full.v",
         "experts.router", "experts.gate", "experts.up", "experts.down"])
    # float32 against float32: the weights move by the rate between the
    # call's two steps and the reference's gradients are all at the first
    for leaf in read["update"].values():
        assert leaf["ok"] and leaf["moved_share"] > 0.99
        assert leaf["moment_distance"] < 1e-3
        assert 0.999 <= leaf["grad_norm_ratio"][0] \
            <= leaf["grad_norm_ratio"][1] <= 1.001


def _the_walk_less_its_first_wave(monkeypatch, which):
    """The held share's walk in waves of 48 rows (two or three a layer at
    `tiny_cell`'s routing, all of the first held expert's in the first)
    with the first wave left out, in the forward (`which` 0) or in the
    backward (1) alone."""
    import jax
    from paddle_tpu.ops import moe_ops
    monkeypatch.setattr(moe_ops, "_held_grad_rows", lambda *_: 48)
    walk, fori_loop = moe_ops._held_walk, jax.lax.fori_loop

    def short_walk(*args):
        # the forward carries (the sum, the rows walked), the backward
        # (dx, dgates, the weights' gradients)
        if len(args[-1]) != 2 + which:
            return walk(*args)
        monkeypatch.setattr(jax.lax, "fori_loop",
                            lambda lo, hi, body, init:
                            fori_loop(lo + 1, hi, body, init))
        try:
            return walk(*args)
        finally:
            monkeypatch.setattr(jax.lax, "fori_loop", fori_loop)

    monkeypatch.setattr(moe_ops, "_held_walk", short_walk)


def test_a_state_left_unchanged_is_not_correct(capsys):
    """A rate of 0: the losses, the counts and Adam's moments are a sound
    call's, and no parameter moves."""
    correct, read = first_call(capsys, learning_rate=0.0)
    assert not correct
    assert read["rel_err"] <= read["rtol"]
    assert read["held_pairs"] == read["reference_held"]
    for leaf in read["update"].values():
        assert leaf["moved_share"] == 0.0 and not leaf["ok"]
        assert leaf["moment_distance"] < 1e-3       # the moments are sound


def test_a_wave_of_the_forward_that_does_not_run_is_not_correct(
        capsys, monkeypatch):
    _the_walk_less_its_first_wave(monkeypatch, 0)
    correct, read = first_call(capsys)
    assert not correct
    assert read["held_pairs"] < read["reference_held"]
    assert read["held_rel_err"] > read["held_rtol"]


def test_a_wave_of_the_backward_that_does_not_run_is_not_correct(
        capsys, monkeypatch):
    """The loss and the counts are a sound call's (they are the
    forward's): the expert whose rows the lost wave held has not moved."""
    _the_walk_less_its_first_wave(monkeypatch, 1)
    correct, read = first_call(capsys)
    assert not correct
    assert read["rel_err"] <= read["rtol"]
    assert read["held_pairs"] == read["reference_held"]
    for key in ("experts.gate", "experts.up", "experts.down"):
        assert read["update"][key]["moved_share"] == 0.0
        assert read["update"][key]["moment_distance"] == 1.0
        assert not read["update"][key]["ok"]
    # and what the lost rows' dx would have carried upstream is missing
    assert read["update"]["window.q"]["moment_distance"] \
        > read["moment_rtol"]


@pytest.mark.parametrize("scale,turned,ok", [
    (1.0, 0.0, True), (1.1, 0.0, True), (18000.0, 0.0, False),
    (0.0, 0.0, False), (1.0, 0.5, False)])
def test_gradients_of_another_size_or_direction_are_not_correct(
        scale, turned, ok):
    """PR 62's first chip run: a loss within 4e-6 of the reference's and
    gradients 18,000 to 99,000 times its. Adam moves an entry by the rate
    whatever the gradient's size, so the leaves all moved: the moments are
    what kept the size, and the direction (`turned`: that share of
    another direction mixed in, as a band one row off does)."""
    pytest.importorskip("jax")
    import numpy as np
    from kinds import train_stream_mapped as kind
    rng = np.random.RandomState(0)
    betas, shape = (0.9, 0.999), (4, 6, 5)
    steps = [rng.randn(*shape) * 1e-3 for _ in range(2)]
    want1, want2 = kind.reference_moments(lambda i: [steps[i]], [betas], 2)
    assert want1[0].shape == shape and want2[0].shape == (4,)
    other = rng.randn(*shape) * 1e-3
    mine = [scale * ((1 - turned) * g + turned * other) for g in steps]
    got1, got2 = kind.reference_moments(lambda i: [mine[i]], [betas], 2)
    moment2 = sum((1 - betas[1]) * betas[1] ** (1 - i) * np.square(g)
                  for i, g in enumerate(mine))
    assert np.allclose(np.sum(moment2, axis=(1, 2)), got2[0])
    before = rng.randn(*shape)
    after = before - 1e-6 * np.sign(mine[0]) * (scale > 0)
    leaves = {"experts.up": kind.update_readings(
        before, after, got1[0], moment2, want1[0], want2[0])}
    correct, read = kind.hold_update(leaves)
    assert correct == ok and read["experts.up"]["ok"] == ok
    if turned:
        assert read["experts.up"]["moment_distance"] > kind.MOMENT_RTOL
    # an expert the reference leaves where it is is held to nothing
    want1[0][1], want2[0][1] = 0.0, 0.0
    again = {"experts.up": kind.update_readings(
        before, after, got1[0] + 1e9 * (np.arange(4) == 1)[:, None, None],
        moment2, want1[0], want2[0])}
    assert kind.hold_update(again)[0] == ok
