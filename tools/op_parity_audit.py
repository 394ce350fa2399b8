"""Op-name parity audit (VERDICT r4 next #8, PARITY row 9).

Greps every operator registration in the reference
(`/root/reference/paddle/fluid/operators`) — the direct macros
(REGISTER_OPERATOR, REGISTER_OP, REGISTER_OP_WITHOUT_GRADIENT,
REGISTER_FILE_READER_OPERATOR, REGISTER_DECORATED_READER_OPERATOR —
op_registry.h:136-174, reader/reader_op_registry.h:92-98) AND the
family-wrapper macros that expand to REGISTER_OPERATOR under the hood
(REGISTER_ELEMWISE_OP elementwise_op.h:145, REGISTER_REDUCE_OP
reduce_op.h:264, REGISTER_COMPARE_OP compare_op.cc:93,
REGISTER_{BINARY,UNARY}_LOGICAL_OP logical_op.cc:113-126, and the
activation FOR_EACH_OP_FUNCTOR / FOR_EACH_INPLACE_OP_FUNCTOR lists at
activation_op.cc:487-520) — and maps each registered name to exactly
one of:

  same_name   — registered under the identical name in core/registry.py
  renamed     — registered under a different repo name (explicit map)
  autodiff    — a `*_grad` op: gradients are a program-to-program transform
                (backward.py + the autodiff pseudo-op in core/lowering.py),
                so grad ops are never separate registrations
  host_module — realized by a host-side module rather than a program op
                (readers, io, CSP channels, distributed bootstrap)
  by_design   — absorbed by the platform per a documented design decision
                (docs/design_decisions.md / PARITY.md)

The audit FAILS (exit 1) if any reference name is unaccounted, and writes
docs/artifacts/op_parity.json with the full classification.
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REF_OPS_DIR = "/root/reference/paddle/fluid/operators"
MACROS = ("REGISTER_OPERATOR", "REGISTER_OP", "REGISTER_OP_WITHOUT_GRADIENT",
          "REGISTER_FILE_READER_OPERATOR",
          "REGISTER_DECORATED_READER_OPERATOR")

# Reference op -> repo op registered under a different name.
RENAMED = {
    "lstm": "dynamic_lstm",
    "gru": "dynamic_gru",
    "read_from_array": "array_read",
    "write_to_array": "array_write",
    "lod_array_length": "array_length",
    "recurrent": "while",  # StaticRNN lowers onto the same scan op
}

# Reference op -> (repo module, note). These are ops only because the
# reference's execution model forces every behavior through an OpDesc; on
# this runtime they are host-side code or executor mechanisms.
HOST_MODULE = {
    "feed": ("core/executor.py", "feeds are jit arguments, not ops"),
    "fetch": ("core/executor.py", "fetches are jit outputs, not ops"),
    "save": ("io.py", "save_vars/save_persistables"),
    "load": ("io.py", "load_vars/load_persistables"),
    "save_combine": ("io.py", "single-archive save (np.savez)"),
    "load_combine": ("io.py", "single-archive load"),
    "delete_var": ("core/scope.py", "Scope lifetime + XLA-owned buffers"),
    "channel_create": ("concurrency.py", "CSP Channel()"),
    "channel_close": ("concurrency.py", "Channel.close()"),
    "channel_send": ("concurrency.py", "Channel.send()"),
    "channel_recv": ("concurrency.py", "Channel.recv()"),
    "go": ("concurrency.py", "go() spawns a host thread"),
    "select": ("concurrency.py", "select() over channels"),
    "parallel_do": ("concurrency.py", "ParallelDo; data-parallel path is "
                    "ParallelExecutor (parallel/parallel_executor.py)"),
    "get_places": ("parallel/mesh.py", "jax.devices()/Mesh axis listing"),
    "lookup_sparse_table": ("host_table.py", "HostEmbeddingTable.lookup"),
    "create_batch_reader": ("reader/decorator.py", "batch()"),
    "create_custom_reader": ("reader/decorator.py", "map_readers()"),
    "create_double_buffer_reader": ("reader/prefetch.py", "double_buffer()"),
    "create_multi_pass_reader": ("reader/decorator.py", "multi_pass()"),
    "create_random_data_generator": ("reader/decorator.py",
                                     "seeded fake-data feeds (benchmark/kinds)"),
    "create_recordio_file_reader": ("recordio.py", "recordio.scan()"),
    "create_shuffle_reader": ("reader/decorator.py", "shuffle()"),
    "create_threaded_reader": ("reader/decorator.py", "xmap_readers()"),
    "open_files": ("reader/decorator.py", "chain + xmap over files"),
    "read": ("layers/io.py", "reader vars feed through the executor"),
}

# Reference op -> documented by-design absorption.
BY_DESIGN = {
    "fc": "layers.fc composes mul + elementwise_add + activation; the "
          "monolithic fc op exists in the reference only for inference "
          "fusion, which XLA performs automatically",
    "tensorrt_engine": "inference acceleration absorbed by XLA AOT "
                       "(PARITY row 37; docs/design_decisions.md)",
    "nccl": "XLA collectives over Mesh (parallel/, PARITY rows 19-20)",
    "gen_nccl_id": "rendezvous via jax.distributed.initialize "
                   "(parallel/distributed.py, PARITY row 20)",
    "send": "pserver RPC replaced by XLA collectives / sync-DP decision "
            "(docs/design_decisions.md, PARITY row 21)",
    "recv": "see send",
    "send_barrier": "see send",
    "fetch_barrier": "see send",
    "prefetch": "pserver-side embedding prefetch -> host_table.py lookup "
                "batching",
    "listen_and_serv": "pserver loop -> sync-DP decision + host_table "
                       "server role (PARITY row 21)",
    "split_byref": "pserver param partitioning -> ZeRO-1 sharding "
                   "(parallel/parallel_executor.py reduce mode)",
    "split_selected_rows": "see split_byref; SelectedRows splitting is "
                           "sharding metadata under GSPMD",
    # LoD bookkeeping: the runtime batches ragged data as dense padded
    # arrays + lod.py metadata; DynamicRNN lowers to ONE lax.scan
    # (ops/rnn_ops.py), so the rank-table choreography has no op analogue.
    "lod_rank_table": "lod.py + scan lowering (PARITY row 7)",
    "lod_tensor_to_array": "scan lowering consumes the padded tensor "
                           "directly",
    "array_to_lod_tensor": "scan emits stacked outputs; lod.py restores "
                           "raggedness",
    "max_sequence_len": "static padded length + lod.py lengths",
    "reorder_lod_tensor_by_rank": "no length-sorting needed: scan is "
                                  "fixed-shape, masks handle padding",
    "shrink_rnn_memory": "fixed-shape scan carries full state; masking "
                         "replaces shrinking",
    "rnn_memory_helper": "autodiff handles scan state (jax.lax.scan VJP)",
    "merge_lod_tensor": "IfElse lowers to lax.cond/select on dense "
                        "tensors (layers/control_flow.py)",
    "split_lod_tensor": "see merge_lod_tensor",
}


# Family-wrapper macro -> (emits op, emits op_grad). Each expands to
# REGISTER_OPERATOR(name) [+ REGISTER_OPERATOR(name_grad)]; a plain grep
# for the direct macros misses every op in these families.
WRAPPERS = {
    "REGISTER_ELEMWISE_OP": True,        # elementwise_op.h:145
    "REGISTER_REDUCE_OP": True,          # reduce_op.h:264
    "REGISTER_COMPARE_OP": False,        # compare_op.cc:93
    "REGISTER_BINARY_LOGICAL_OP": False,  # logical_op.cc:113
    "REGISTER_UNARY_LOGICAL_OP": False,   # logical_op.cc:126
}


def reference_op_names():
    direct = re.compile(r"(?:%s)\(\s*([a-z0-9_]+)" % "|".join(MACROS))
    wrapper = re.compile(r"(%s)\(\s*([a-z0-9_]+)" % "|".join(WRAPPERS))
    names = set()
    for root, _, files in os.walk(REF_OPS_DIR):
        for fn in files:
            if not fn.endswith((".cc", ".cu")):
                continue
            with open(os.path.join(root, fn), errors="replace") as f:
                text = f.read()
            names.update(direct.findall(text))
            for macro, op in wrapper.findall(text):
                if op == "op_type":
                    continue  # the macro definition itself
                names.add(op)
                if WRAPPERS[macro]:
                    names.add(op + "_grad")
    # The activation families register through indirection lists:
    # FOR_EACH_OP_FUNCTOR(REGISTER_ACTIVATION_OP) and
    # FOR_EACH_INPLACE_OP_FUNCTOR(REGISTER_INPLACE_ACTIVATION_OP) expand
    # __macro(CamelName, snake_name) -> snake_name + snake_name_grad.
    with open(os.path.join(REF_OPS_DIR, "activation_op.cc"),
              errors="replace") as f:
        act = f.read()
    for lst in re.findall(
            r"#define FOR_EACH(?:_INPLACE)?_OP_FUNCTOR\(__macro\)([^#]*)",
            act):
        for _, snake in re.findall(r"__macro\(([A-Za-z0-9]+),\s*([a-z0-9_]+)\)",
                                   lst):
            names.add(snake)
            names.add(snake + "_grad")
    names.discard("op_name")  # macro documentation text, not a registration
    return sorted(names)


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu  # noqa: F401  (populates the registry)
    from paddle_tpu.core import registry

    repo = set(registry.registered_ops())
    ref = reference_op_names()
    rows, unaccounted = {}, []
    for name in ref:
        if name in repo:
            rows[name] = {"status": "same_name"}
        elif name.endswith("_grad") and (name[:-5] in repo
                                         or name[:-5] in RENAMED
                                         or name[:-5] in BY_DESIGN
                                         or name[:-5] in HOST_MODULE):
            rows[name] = {"status": "autodiff",
                          "note": "gradient ops are emitted by backward.py "
                                  "/ jax.grad, never registered"}
        elif name in RENAMED:
            rows[name] = {"status": "renamed", "repo_op": RENAMED[name]}
        elif name in HOST_MODULE:
            mod, note = HOST_MODULE[name]
            rows[name] = {"status": "host_module", "module": mod,
                          "note": note}
        elif name in BY_DESIGN:
            rows[name] = {"status": "by_design", "note": BY_DESIGN[name]}
        else:
            rows[name] = {"status": "UNACCOUNTED"}
            unaccounted.append(name)

    counts = {}
    for r in rows.values():
        counts[r["status"]] = counts.get(r["status"], 0) + 1
    out = {
        "reference_registration_macros": list(MACROS),
        "reference_ops_total": len(ref),
        "repo_ops_registered": len(repo),
        "counts": counts,
        "unaccounted": unaccounted,
        "ops": rows,
    }
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "artifacts", "op_parity.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"total": len(ref), "counts": counts,
                      "unaccounted": unaccounted}))
    if unaccounted:
        print("AUDIT FAILED: unaccounted reference ops", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
