"""What the builders of `models/transformer.py` BUILD for every cell of
the benchmark, held to a record written once: `built_programs_at_pr59.json`.

For each of the ten serve configurations of `benchmark/configs/`, through
its own mapping (`benchmark/kinds/_model*.py`) and at its published widths:
`BlockSpec.to_dict()` (what its bundle's serving.json records), the decode
step's feeds with their shapes, and a digest of the step program; and the
same digest of its prefill program at its cell's smallest and largest
bucket, built as `io.export_decode_model` builds it (`io.prefill_program`).
For a train configuration, the digest of the cell's training program
(`transformer_lm_loss` under the mapping's optimizer). The cells added
since the record was written are held in a second one,
`built_programs_since_pr62.json` (Mellum 2's training program). A digest is the
sha256 of the program's ops with their attrs, in order (`ops_sha256`: what
`tests/serve_blocks_at_pr57.json` held, carried over), and a second one,
of every op's input and output names beside them, the parameters with
their shapes and the fetch list (`wiring_sha256`). Building a `Program`
allocates nothing: published widths cost IR only.

The same ops with the same attrs over the same variables in the same order
lower to the same XLA: a change to the Python that BUILDS the programs
which leaves every digest alone leaves the chip's computation alone, in
all eleven cells. A change that means to move a program regenerates the
record with this file's own code, `python tests/test_built_programs.py
--write`, and says so.
"""

import hashlib
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, ".."))
RECORD = os.path.join(HERE, "built_programs_at_pr59.json")
#: the cells added since that record was written, in a record of their
#: own (the first stays as it is: a PR that moves none of its programs
#: leaves the file alone)
RECORD_SINCE = os.path.join(HERE, "built_programs_since_pr62.json")


def _json(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _cells():
    """{configuration: (its file's contents, its cell's traffic)} for the
    configurations the manifest's cells run, in the manifest's order."""
    manifest = _json("BENCHMARK.json")
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    return {w["config"]: (_json(files[w["config"]]),
                          _json(f"benchmark/traffic/{w['traffic']}.json"))
            for w in manifest["workloads"]}


def _digest(program, fetches):
    """`n_ops`, `ops_sha256` (types and attrs, in order) and
    `wiring_sha256` (those with every op's inputs and outputs, the
    parameters with their shapes, and the fetch list)."""
    blk = program.global_block
    attrs = [json.loads(json.dumps(op.attrs, sort_keys=True, default=str))
             for op in blk.ops]
    ops = [[op.type, a] for op, a in zip(blk.ops, attrs)]
    wiring = {"ops": [[op.type, a, op.inputs, op.outputs]
                      for op, a in zip(blk.ops, attrs)],
              "params": sorted([v.name, list(v.shape), v.dtype]
                               for v in program.all_parameters()),
              "fetches": list(fetches)}

    def sha(obj):
        return hashlib.sha256(
            json.dumps(obj, sort_keys=True).encode()).hexdigest()

    return {"n_ops": len(ops), "ops_sha256": sha(ops),
            "wiring_sha256": sha(wiring)}


def built(name, cfg, traffic):
    """One configuration's record, from the tree's own builders."""
    import paddle_tpu as pt
    from paddle_tpu import io as pio
    from paddle_tpu.models import transformer as tfm

    bench = os.path.join(ROOT, "benchmark")
    added = bench not in sys.path
    if added:       # behind what is there: nothing of tier-1 is shadowed
        sys.path.append(bench)
    try:
        mapping = importlib.import_module("kinds." + cfg.get(
            "harness", {}).get("mapping", "_model"))
        sz = mapping.sizes(cfg)
        if "train" in cfg:      # (main, startup, loss[, what else a step
            pt.core.program.reset_unique_names()          # fetches])
            main, _, *fetched = mapping.build_trainer(
                pt, sz, int(traffic["seq_len"]), 0, cfg["train"])
            return {"train": _digest(main, [v.name for v in fetched])}
        srv = cfg["serving"]
        block = tfm.BlockSpec.of(sz.get("block"))
        block_size = int(srv["block_size"])
        extra = {}
        if block.window:
            extra["window_pool_blocks"] = int(srv["slots"]) * (
                block.window // block_size + 1) + 1
        step = dict(
            n_layers=sz["n_layers"], d_model=sz["d_model"],
            n_heads=sz["n_heads"], d_ff=sz["d_ff"],
            max_context=sz["max_len"], slots=int(srv["slots"]),
            block_size=block_size, pool_blocks=int(srv["pool_blocks"]),
            max_blocks_per_seq=-(-int(sz["max_len"]) // block_size),
            block=block, **extra)

        def step_program(**outs):
            pt.core.program.reset_unique_names()
            main = pt.Program()
            with pt.program_guard(main, pt.Program()):
                logits, pool_outs, feeds = tfm.transformer_decode_step(
                    sz["vocab"], **step, **outs)
            fetches = [logits.name] + [v.name for pools in pool_outs
                                       for v in pools] \
                + [v.name for out in outs.values() for v in out]
            return main, feeds, fetches

        # the step alone, as `serve_blocks_at_pr57.json` held it, and as
        # the export builds it: with the counters', the routes' and the
        # selections' fetches behind the pools
        main, feeds, fetches = step_program()
        blk = main.global_block
        out = {"block": block.to_dict(),
               "feeds": [[n, list(blk.var(n).shape)] for n in feeds],
               **_digest(main, fetches),
               "step": _digest(*step_program(
                   moe_stats_out=[], moe_routes_out=[],
                   selected_out=[])[::2]),
               "prefill": {}}
        buckets = sorted(int(b) for b in traffic["prefill_buckets"])
        for bound in sorted({buckets[0], buckets[-1]}):
            pt.core.program.reset_unique_names()
            program, targets = pio.prefill_program(
                block, bound, vocab=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"])
            out["prefill"][str(bound)] = _digest(program, targets)
        return out
    finally:
        if added:
            sys.path.remove(bench)


def _record(path=None):
    """The record of `path`; None: both records' cells."""
    out = {}
    for file in (path,) if path else (RECORD, RECORD_SINCE):
        if os.path.exists(file):
            with open(file) as f:
                out.update(json.load(f))
    return out


# the record's names, read at collection: a plain file, the same in every
# worker
@pytest.mark.parametrize("name", sorted(_record()))
def test_a_configuration_builds_what_it_built(name):
    cfg, traffic = _cells()[name]
    got = json.loads(json.dumps(built(name, cfg, traffic)))
    then = _record()[name]
    for key in then:    # the smaller parts first: they name what moved
        assert got[key] == then[key], (name, key)
    assert got == then


def test_the_record_holds_every_cell_and_what_pr57_held():
    """Every configuration a cell runs is in the record, and the nine
    that `serve_blocks_at_pr57.json` held are there as it held them."""
    record = _record()
    assert sorted(record) == sorted(_cells())
    assert len(_record(RECORD)) == 11 and len(record) == 13
    with open(os.path.join(HERE, "serve_blocks_at_pr57.json")) as f:
        then = json.load(f)
    assert len(then) == 9
    for name, entry in then.items():
        assert {k: record[name][k] for k in entry} == entry, name


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        sys.exit("usage: python tests/test_built_programs.py --write")
    sys.path.insert(0, ROOT)
    held = _record(RECORD)      # (delete a record to write it anew)
    for path, mine in ((RECORD, lambda name: name in held or not held),
                       (RECORD_SINCE, lambda name: name not in held)):
        with open(path, "w") as f:
            json.dump({name: built(name, cfg, traffic)
                       for name, (cfg, traffic) in sorted(_cells().items())
                       if mine(name)}, f, indent=1, sort_keys=True)
            f.write("\n")
