"""Model persistence: save/load variables, inference export, checkpoints.

≙ reference python/paddle/fluid/io.py (save/load_vars/params/persistables
:64-234, save/load_inference_model :301-378, checkpoint subsystem :466-735).
The reference runs save/load *ops* through an executor; here persistence is
host-side .npz (one file per var, or combined) plus the program JSON —
functionally identical artifacts (dir of vars + serialized program), no
device roundtrip beyond fetching arrays.
"""

from __future__ import annotations

import json
import os
import re
import shutil
from typing import Dict, List, Optional, Sequence

import numpy as np

from .core.program import Program, VarDesc, default_main_program
from .core.scope import Scope, global_scope
from .resilience import FaultInjected, faults
from .resilience import manifest as _manifest
from .resilience.manifest import VerificationError as _VerificationError
from .resilience.retry import RetryPolicy, retry_call

__all__ = [
    "save_vars", "save_params", "save_persistables", "load_vars",
    "load_params", "load_persistables", "save_inference_model",
    "load_inference_model", "get_inference_program",
    "export_serving_model", "export_decode_model", "load_serving_model",
    "save_checkpoint", "load_checkpoint", "clean_checkpoint",
    "get_latest_checkpoint_serial", "CheckpointCorruptError",
    "PlanMismatchError", "plan_stamp", "read_plan_stamp",
    "check_plan_stamp", "PLAN_STAMP_KEYS",
]

SUCCESS_MARK_FILENAME = "_SUCCESS"
SERVING_WEIGHTS_FILENAME = "weights.npz"
CHECKPOINT_PREFIX = "checkpoint"


class CheckpointCorruptError(_VerificationError):
    """An explicitly requested checkpoint failed manifest verification
    (auto-selection never raises this — it falls back to the newest
    serial that verifies, quarantining the corrupt one)."""


#: load-time verification gate (PT_CKPT_VERIFY): shared with
#: host_table.load so the opt-out covers every verification site
_verify_on_load = _manifest.verify_on_load


#: transient-FS retry for checkpoint reads. Deterministic failures are
#: excluded on purpose: a missing var file (FileNotFoundError) and
#: integrity failures (VerificationError — manifest mismatch, mixed
#: layouts) can only fail identically on every attempt
_LOAD_RETRY = RetryPolicy(
    retries=2, base_delay=0.05, max_delay=0.5,
    retry_on=lambda e: isinstance(e, OSError)
    and not isinstance(e, (FileNotFoundError, _VerificationError)))


def _is_persistable(var: VarDesc) -> bool:
    return var.persistable


def _is_parameter(var: VarDesc) -> bool:
    return var.is_parameter


# ---------------------------------------------------------------------------
# multi-host sharded array pieces
#
# ≙ the reference's per-pserver checkpoint shards (go/pserver/service.go:346
# saves only the rows that pserver owns; the trainer side reassembles via
# load_persist_vars_without_grad, io.py:545). TPU-native: a var's value can
# be a jax.Array laid out by GSPMD across processes; each process persists
# exactly its addressable, replica-0 shards as `<name>.shard.<slices>.npy`
# plus one `<name>.meta.json` (global shape/dtype), and the loader
# reassembles the global value from whatever pieces the dir holds.
# ---------------------------------------------------------------------------

def _shard_slices(val, sh):
    """Normalize a Shard.index into ((start, stop), ...) over global dims."""
    out = []
    for dim, sl in zip(val.shape, sh.index):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def _atomic_save(path: str, arr) -> None:
    faults.crash_point("io_crash")
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        np.save(f, arr)
    hit = faults.fire("io_write_truncate")
    if hit is not None:
        # torn write: half the bytes make it to the FINAL name before the
        # "process dies" — the exact artifact a power loss can leave that
        # tmp+replace alone cannot guard against (the manifest can)
        size = os.path.getsize(tmp)
        with open(tmp, "r+b") as f:
            f.truncate(size // 2)
        os.replace(tmp, path)
        raise FaultInjected("io_write_truncate", hit)
    os.replace(tmp, path)


def _save_sharded(dirname: str, base: str, val) -> None:
    # meta is identical on every process; atomic replace makes the
    # concurrent writes idempotent and refreshes any stale file
    meta = {"shape": list(val.shape), "dtype": str(val.dtype)}
    meta_path = os.path.join(dirname, base + ".meta.json")
    tmp = meta_path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(meta, f)
    os.replace(tmp, meta_path)
    for sh in val.addressable_shards:
        if sh.replica_id != 0:  # exactly one owner per distinct slice
            continue
        spans = _shard_slices(val, sh)
        tag = "x".join(f"{a}_{b}" for a, b in spans) or "scalar"
        _atomic_save(os.path.join(dirname, f"{base}.shard.{tag}.npy"),
                     np.asarray(sh.data))


def _load_sharded(dirname: str, base: str):
    meta_path = os.path.join(dirname, base + ".meta.json")
    if not os.path.exists(meta_path):
        return None
    with open(meta_path) as f:
        meta = json.load(f)
    from .core.types import np_dtype
    shape = tuple(meta["shape"])
    out = np.zeros(shape, np_dtype(meta["dtype"]))
    prefix = base + ".shard."
    found = 0
    filled = 0
    for name in sorted(os.listdir(dirname)):
        if not (name.startswith(prefix) and name.endswith(".npy")):
            continue
        tag = name[len(prefix):-len(".npy")]
        piece = np.load(os.path.join(dirname, name))
        if tag == "scalar":
            idx = ()
            extents = shape
        else:
            spans = [tuple(int(x) for x in p.split("_"))
                     for p in tag.split("x")]
            idx = tuple(slice(a, b) for a, b in spans)
            extents = tuple(b - a for a, b in spans)
        if tuple(piece.shape) != tuple(extents):
            raise IOError(
                f"load_vars: shard piece {name!r} has shape {piece.shape}, "
                f"expected {extents} — the directory mixes saves from "
                "different runs/layouts; re-save into a fresh directory")
        out[idx] = piece
        found += 1
        filled += int(piece.size)
    if not found:
        return None
    # pieces are disjoint by construction (one replica-0 owner per slice),
    # so element counting detects both missing pieces and stale extras
    # from a different process layout without a full-shape bool mask
    total = int(np.prod(shape)) if shape else 1
    if filled != total:
        raise FileNotFoundError(
            f"load_vars: sharded var {base!r} in {dirname!r} covers "
            f"{filled}/{total} elements — missing pieces (were all "
            "processes' shard files gathered into this directory?) or "
            "stale pieces from an older save with a different layout")
    return out


def _is_cross_process(val) -> bool:
    import jax
    return isinstance(val, jax.Array) and not val.is_fully_addressable


def _npy_header(path: str):
    """(shape, dtype) straight from an .npy header — no data read. The
    streaming reshard and the gather guardrail size a serial dir from
    headers; loading the arrays to measure them would BE the OOM."""
    with open(path, "rb") as f:
        version = np.lib.format.read_magic(f)
        if version == (1, 0):
            shape, _fortran, dtype = np.lib.format.read_array_header_1_0(f)
        elif version == (2, 0):
            shape, _fortran, dtype = np.lib.format.read_array_header_2_0(f)
        else:  # pragma: no cover — future npy format versions
            shape, _fortran, dtype = np.lib.format._read_array_header(
                f, version)
    return tuple(shape), dtype


def serial_var_sources(serial_dir: str) -> dict:
    """Header-only description of every persisted var in a serial dir:
    ``{base: {"shape", "dtype", "pieces": [{"path", "index"}]}}`` where
    a full-array source has ``index=None`` and a multi-process shard
    piece carries its global ``((start, stop), ...)`` spans. Same
    precedence as the loaders (shard pieces win over a same-named full
    file) and the same coverage contract as ``_load_sharded`` — missing
    pieces fail loudly here, before any byte moves."""
    sources: dict = {}
    names = sorted(os.listdir(serial_dir))
    sharded = [n[:-len(".meta.json")] for n in names
               if n.endswith(".meta.json")]
    for name in names:
        if name.endswith(".npy") and ".shard." not in name:
            path = os.path.join(serial_dir, name)
            shape, dtype = _npy_header(path)
            sources[name[:-len(".npy")]] = {
                "shape": shape, "dtype": dtype,
                "pieces": [{"path": path, "index": None}]}
    from .core.types import np_dtype
    for base in sharded:
        with open(os.path.join(serial_dir, base + ".meta.json")) as f:
            meta = json.load(f)
        shape = tuple(int(d) for d in meta["shape"])
        prefix = base + ".shard."
        pieces, filled = [], 0
        for name in names:
            if not (name.startswith(prefix) and name.endswith(".npy")):
                continue
            tag = name[len(prefix):-len(".npy")]
            if tag == "scalar":
                spans = ()
            else:
                spans = tuple(tuple(int(x) for x in p.split("_"))
                              for p in tag.split("x"))
            n = 1
            for a, b in spans:
                n *= (b - a)
            filled += n
            pieces.append({"path": os.path.join(serial_dir, name),
                           "index": spans})
        if not pieces:
            continue
        total = int(np.prod(shape)) if shape else 1
        if filled != total:
            raise FileNotFoundError(
                f"serial_var_sources: sharded var {base!r} in "
                f"{serial_dir!r} covers {filled}/{total} elements — "
                "missing pieces (were all processes' shard files "
                "gathered into this directory?) or stale pieces from an "
                "older save with a different layout")
        sources[base] = {"shape": shape,
                         "dtype": np_dtype(meta["dtype"]),
                         "pieces": pieces}
    return sources


def estimate_serial_host_bytes(serial_dir: str) -> int:
    """Host bytes a full gather of this serial dir materializes: the sum
    of every var's GLOBAL nbytes, from headers alone."""
    total = 0
    for info in serial_var_sources(serial_dir).values():
        n = 1
        for d in info["shape"]:
            n *= int(d)
        total += n * np.dtype(info["dtype"]).itemsize
    return total


# ---------------------------------------------------------------------------
# save/load vars
# ---------------------------------------------------------------------------

def save_vars(executor=None, dirname: str = "", main_program: Optional[Program] = None,
              vars: Optional[Sequence] = None, predicate=None,
              filename: Optional[str] = None, scope: Optional[Scope] = None):
    """io.py:64 save_vars: one .npy per var, or a single combined file."""
    main_program = main_program or default_main_program()
    scope = scope or global_scope()
    if vars is None:
        vars = [v for v in main_program.list_vars() if (predicate or _is_persistable)(v)]
    vars = [main_program.global_block.var(v) if isinstance(v, str) else v
            for v in vars]
    os.makedirs(dirname, exist_ok=True)
    values = {v.name: scope.find_var(v.name) for v in vars}
    absent = [n for n, val in values.items() if val is None]
    if absent:
        # symmetric with load_vars' strictness: a partial save would only
        # surface at load time with a misleading error
        raise ValueError(
            f"save_vars: {len(absent)} variable(s) have no value in the "
            f"scope (run the startup program first?): {absent[:5]}"
            f"{'...' if len(absent) > 5 else ''}")
    # device-resident state: scope values are jax.Arrays that may still be
    # executing (async dispatch). ONE collective wait here lets in-flight
    # steps and D2H transfers overlap, instead of the per-var np.asarray
    # below serializing a sync per array; it also pins the checkpoint
    # semantics — bytes are materialized from a SETTLED step boundary, so
    # the resilience manifests digest stable data.
    import jax
    jax.block_until_ready([v for v in values.values()
                           if isinstance(v, jax.Array)])
    if filename is not None:
        cross = [n for n, v in values.items() if _is_cross_process(v)]
        if cross:
            raise ValueError(
                "save_vars(filename=...): combined-file saves need fully "
                f"addressable values, but {cross[:3]} are sharded across "
                "processes — use the per-var layout (filename=None), which "
                "persists each process's own shards")
        # every value is fully addressable (checked above), so rank 0's
        # copy suffices — and in a multi-process run all ranks share the
        # filesystem: concurrent np.savez of the SAME file would corrupt
        # the archive. Mirrors the per-var path's rank-0 gating.
        if jax.process_count() == 1 or jax.process_index() == 0:
            np.savez(os.path.join(dirname, filename),
                     **{n: np.asarray(v) for n, v in values.items()})
        if jax.process_count() > 1:
            # barrier AFTER the rank-0 write (ADVICE r4 #3): without it a
            # non-zero rank returning immediately can read a partial or
            # absent archive before rank 0 finishes writing
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("pt_save_vars_combined")
        return
    import jax
    multi = jax.process_count() > 1
    rank0 = not multi or jax.process_index() == 0
    existing = os.listdir(dirname) if rank0 else []

    def clean(base, this_layout):
        # remove files the coming write will NOT atomically replace: the
        # other layout entirely (a stale .npy would shadow shards at load;
        # stale shards would blend into assembly), and — for a sharded
        # save — old shard pieces whose spans this run's processes may not
        # overwrite. Same-layout .npy is left for _atomic_save's
        # os.replace, so a crash mid-save never destroys the previous
        # good full-array file; a crashed sharded re-save is detectable
        # (the loader's element-count check fails loudly).
        for stale in existing:
            other_layout = (
                (stale == base + ".npy") if this_layout == "sharded"
                else (stale == base + ".meta.json"
                      or stale.startswith(base + ".shard.")))
            stale_shards = (this_layout == "sharded"
                            and stale.startswith(base + ".shard."))
            if other_layout or stale_shards:
                try:
                    os.remove(os.path.join(dirname, stale))
                except FileNotFoundError:
                    pass

    if rank0:
        for n, val in values.items():
            clean(n.replace("/", "__"),
                  "sharded" if _is_cross_process(val) else "npy")
    if multi:
        # nobody writes until rank 0 finished deleting — otherwise a
        # faster rank's fresh shard piece could be swept as "stale"
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_save_vars_clean")

    for n, val in values.items():
        base = n.replace("/", "__")
        if _is_cross_process(val):
            _save_sharded(dirname, base, val)
        elif rank0:
            # fully-addressable values are replicated across processes by
            # construction (the sharded route owns everything GSPMD laid
            # out); process 0 is the single writer, atomically
            _atomic_save(os.path.join(dirname, base + ".npy"),
                         np.asarray(val))
    if multi:
        # nobody returns (and possibly reloads) until every writer — rank
        # 0's .npy files AND all shard pieces — has hit the filesystem
        multihost_utils.sync_global_devices("paddle_tpu_save_vars_done")


def save_params(executor=None, dirname: str = "", main_program=None,
                filename=None, scope=None):
    return save_vars(executor, dirname, main_program, None, _is_parameter,
                     filename, scope)


def save_persistables(executor=None, dirname: str = "", main_program=None,
                      filename=None, scope=None):
    out = save_vars(executor, dirname, main_program, None, _is_persistable,
                    filename, scope)
    # host-RAM embedding tables live OUTSIDE the scope (host_table.py);
    # every process persists its own vocab shard beside the program vars
    # so checkpoints/auto-resume restore them too (≙ the pserver saving
    # its table shards, go/pserver/service.go:346)
    from . import host_table as _ht
    _ht.save_all(dirname, main_program or default_main_program())
    return out


def load_vars(executor=None, dirname: str = "", main_program=None, vars=None,
              predicate=None, filename=None, scope=None):
    """io.py:129 load_vars."""
    main_program = main_program or default_main_program()
    scope = scope or global_scope()
    if vars is None:
        vars = [v for v in main_program.list_vars() if (predicate or _is_persistable)(v)]
    vars = [main_program.global_block.var(v) if isinstance(v, str) else v
            for v in vars]
    if filename is not None:
        # np.savez appends ".npz" to suffixless names on save: mirror it
        if not filename.endswith(".npz"):
            filename = filename + ".npz"
        data = np.load(os.path.join(dirname, filename), allow_pickle=False)
        missing = [v.name for v in vars if v.name not in data]
        if missing:
            # ≙ load_op.cc PADDLE_ENFORCE on a missing variable: loading
            # nothing silently would "resume" training from scratch
            raise FileNotFoundError(
                f"load_vars: {len(missing)} variable(s) absent from "
                f"{filename!r}: {missing[:5]}{'...' if len(missing) > 5 else ''}")
        for v in vars:
            scope.set_var(v.name, data[v.name])
        return
    missing = []
    for v in vars:
        base = v.name.replace("/", "__")
        path = os.path.join(dirname, base + ".npy")
        has_npy = os.path.exists(path)
        has_shards = os.path.exists(os.path.join(dirname,
                                                 base + ".meta.json"))
        if has_npy and has_shards:
            # both layouts present = an interrupted re-save with a changed
            # sharding; guessing which is current would silently restore
            # stale values (save_vars cleans the other layout on success)
            raise _VerificationError(
                f"load_vars: {v.name!r} has BOTH a full .npy and shard "
                f"pieces in {dirname!r} — the directory mixes saves with "
                "different layouts; delete the stale layout or re-save")
        if has_npy:
            scope.set_var(v.name, np.load(path))
        else:
            assembled = _load_sharded(dirname, base)
            if assembled is not None:
                scope.set_var(v.name, assembled)
            else:
                missing.append(v.name)
    if missing:
        raise FileNotFoundError(
            f"load_vars: no saved file for {len(missing)} variable(s) in "
            f"{dirname!r}: {missing[:5]}{'...' if len(missing) > 5 else ''} "
            "(wrong dirname, or the program names differ from the saved "
            "run's — e.g. programs built after others in the same process "
            "get different unique_name suffixes)")


def load_params(executor=None, dirname: str = "", main_program=None,
                filename=None, scope=None):
    return load_vars(executor, dirname, main_program, None, _is_parameter,
                     filename, scope)


def load_persistables(executor=None, dirname: str = "", main_program=None,
                      filename=None, scope=None):
    out = load_vars(executor, dirname, main_program, None, _is_persistable,
                    filename, scope)
    from . import host_table as _ht
    _ht.load_all(dirname, main_program or default_main_program())
    return out


# ---------------------------------------------------------------------------
# inference model export (io.py:301 save_inference_model)
# ---------------------------------------------------------------------------

def get_inference_program(target_vars, main_program=None) -> Program:
    main_program = main_program or default_main_program()
    if not isinstance(target_vars, (list, tuple)):
        target_vars = [target_vars]
    pruned = main_program.clone(for_test=True).prune(
        targets=[t.name if isinstance(t, VarDesc) else t for t in target_vars])
    return pruned


def save_inference_model(dirname: str, feeded_var_names: Sequence[str],
                         target_vars, executor=None, main_program=None,
                         model_filename: Optional[str] = None,
                         params_filename: Optional[str] = None, scope=None):
    main_program = main_program or default_main_program()
    if not isinstance(target_vars, (list, tuple)):
        target_vars = [target_vars]
    target_names = [t.name if isinstance(t, VarDesc) else t for t in target_vars]
    pruned = main_program.clone(for_test=True).prune(targets=target_names,
                                                     feeds=feeded_var_names)
    os.makedirs(dirname, exist_ok=True)
    meta = {"program": pruned.to_dict(), "feed_names": list(feeded_var_names),
            "fetch_names": target_names}
    with open(os.path.join(dirname, model_filename or "__model__.json"), "w") as f:
        json.dump(meta, f)
    save_persistables(executor, dirname, pruned,
                      filename=params_filename, scope=scope)
    # same manifest treatment as checkpoints: a deployed model dir can be
    # verified (and a torn copy detected) before it serves traffic
    import jax
    if jax.process_count() > 1:
        # save_vars barriers internally, but host-table rank shards are
        # written AFTER that barrier (save_persistables tail) — without
        # this sync rank 0's manifest scan could miss a peer's file
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("pt_save_inference_manifest")
    if jax.process_count() == 1 or jax.process_index() == 0:
        _manifest.write_manifest(dirname, layout="inference")
    return target_names


def load_inference_model(dirname: str, executor=None,
                         model_filename: Optional[str] = None,
                         params_filename: Optional[str] = None, scope=None):
    if _verify_on_load() and _manifest.read_manifest(dirname) is not None:
        status, problems = _manifest.verify_dir(dirname)
        if status == "corrupt":
            raise CheckpointCorruptError(
                f"inference model dir {dirname!r} failed manifest "
                f"verification: {'; '.join(problems[:5])}")
    with open(os.path.join(dirname, model_filename or "__model__.json")) as f:
        meta = json.load(f)
    program = Program.from_dict(meta["program"])
    load_persistables(executor, dirname, program, filename=params_filename,
                      scope=scope)
    fetch_vars = [program.global_block.var(n) for n in meta["fetch_names"]]
    return program, meta["feed_names"], fetch_vars


# ---------------------------------------------------------------------------
# AOT serving export
# ---------------------------------------------------------------------------

def export_serving_model(dirname: str, feeded_var_names: Sequence[str],
                         target_vars, executor=None, main_program=None,
                         scope: Optional[Scope] = None, batch_size: int = 1,
                         length_buckets: Optional[Sequence[int]] = None):
    """Ahead-of-time serving export (≙ the deployment role of
    inference/analysis + PaddlePredictor, paddle_inference_api.h).

    Prunes the program to the targets, binds the trained weights as
    CONSTANTS, jit-compiles the forward, and serializes it with
    jax.export (StableHLO). The artifact is self-contained: serving needs
    only jax + the files written here — no program interpreter, no
    framework, no weight files. Shape-specialized to `batch_size` (XLA
    AOT is static-shape; export per served batch size).

    `length_buckets`: a sorted set of pad bounds for feeds with a
    symbolic (non-batch) length dim. One artifact is exported PER bucket
    (``serving_len{L}.stablehlo``) with every symbolic length dim pinned
    to the bound, so the online engine (paddle_tpu/serving/) serves
    arbitrary lengths with a bounded executable set — the same lever as
    reader/bucketing.py on the training side. Without it a symbolic
    non-batch dim is an error, as before.

    serving.json records, per bucket, the feed AND fetch specs (name /
    shape / dtype, from the exported module's out_avals) so output
    introspection exists without running the model and the serving
    batcher can preallocate scatter buffers.
    """
    import jax
    import jax.numpy as jnp
    from .core import lowering
    from .core.types import device_dtype
    from .core.types import np_dtype

    main_program = main_program or default_main_program()
    scope = scope or global_scope()
    if not isinstance(target_vars, (list, tuple)):
        target_vars = [target_vars]
    target_names = [t.name if isinstance(t, VarDesc) else t
                    for t in target_vars]
    pruned = main_program.clone(for_test=True).prune(
        targets=target_names, feeds=feeded_var_names)

    state = {}
    for var in pruned.list_vars():
        if var.persistable and scope.has_var(var.name):
            v = scope.find_var(var.name)
            if v is not None:
                state[var.name] = jnp.asarray(v)
    step, _ = lowering.build_step_fn(pruned, list(feeded_var_names),
                                     target_names, [], is_test=True)
    key = jax.random.PRNGKey(0)

    def serve(*feeds):
        env = dict(zip(feeded_var_names, feeds))
        fetches, _ = step(state, env, key)
        return fetches

    # per-feed shape templates: the leading -1 is layers.data's symbolic
    # batch dim (pinned to batch_size); any OTHER -1 is a length dim that
    # needs a bucket bound
    templates = []
    var_dims: Dict[str, List[int]] = {}
    for name in feeded_var_names:
        var = pruned.global_block.var(name)
        dims = tuple(int(s) for s in var.shape)
        shape = list(dims)
        if shape and shape[0] == -1:
            shape[0] = batch_size
        lens = [i for i, s in enumerate(shape) if s < 0]
        if lens and not length_buckets:
            raise ValueError(
                f"export_serving_model: feed {name!r} has symbolic dims "
                f"{dims}; AOT export needs fully static shapes — pad or "
                "declare the feed with concrete sizes, or pass "
                "length_buckets=(...) to export one artifact per pad bound")
        if lens:
            var_dims[name] = lens
        templates.append((name, shape, np_dtype(device_dtype(var.dtype)),
                          bool(dims) and dims[0] == -1))

    from .core.compat import jax_export

    def _export_one(length: Optional[int]):
        example, alt, feeds_meta = [], [], []
        for name, shape, dt, is_batch in templates:
            concrete = [length if s < 0 else s for s in shape]
            example.append(jax.ShapeDtypeStruct(tuple(concrete), dt))
            bumped = list(concrete)
            if is_batch:
                bumped[0] = batch_size + 1
            alt.append(jax.ShapeDtypeStruct(tuple(bumped), dt))
            feeds_meta.append({"name": name, "shape": concrete,
                               "dtype": np.dtype(dt).name,
                               "batch_major": is_batch})
        exported = jax_export().export(jax.jit(serve))(*example)
        # ground-truth batch-major flags for the fetches: abstractly
        # re-evaluate at batch_size+1 and keep only the fetches whose
        # leading dim TRACKS the batch — a fetch whose leading dim merely
        # coincides with batch_size must not be scattered per request
        try:
            alt_avals = list(jax.eval_shape(serve, *alt))
        except Exception:  # program pins the batch: shape heuristic only
            alt_avals = None
        fetch_meta = []
        for j, (n, aval) in enumerate(zip(target_names,
                                          exported.out_avals)):
            bm = bool(aval.shape) and int(aval.shape[0]) == batch_size
            if bm and alt_avals is not None:
                a = alt_avals[j].shape
                bm = bool(a) and int(a[0]) == batch_size + 1
            fetch_meta.append({"name": n,
                               "shape": [int(s) for s in aval.shape],
                               "dtype": np.dtype(aval.dtype).name,
                               "batch_major": bm})
        return exported.serialize(), feeds_meta, fetch_meta

    os.makedirs(dirname, exist_ok=True)
    buckets_meta = []
    if var_dims and length_buckets:
        for bound in sorted(int(b) for b in length_buckets):
            blob, feeds_meta, fetch_meta = _export_one(bound)
            fn = f"serving_len{bound}.stablehlo"
            with open(os.path.join(dirname, fn), "wb") as f:
                f.write(blob)
            buckets_meta.append({"length": bound, "file": fn,
                                 "feeds": feeds_meta,
                                 "fetches": fetch_meta})
        # compat artifact for single-shape loaders (load_serving_model):
        # the largest bucket, under the historical filename
        with open(os.path.join(dirname, "serving.stablehlo"), "wb") as f:
            f.write(blob)
        base = buckets_meta[-1]
    else:
        blob, feeds_meta, fetch_meta = _export_one(None)
        with open(os.path.join(dirname, "serving.stablehlo"), "wb") as f:
            f.write(blob)
        base = {"length": None, "file": "serving.stablehlo",
                "feeds": feeds_meta, "fetches": fetch_meta}
        buckets_meta = [base]
    with open(os.path.join(dirname, "serving.json"), "w") as f:
        json.dump({"feeds": base["feeds"], "fetch_names": target_names,
                   "fetches": base["fetches"], "batch_size": batch_size,
                   "buckets": buckets_meta, "var_dims": var_dims}, f)
    return dirname


#: what a decode bundle's `weight_dtype` may be: the matrices as the
#: scope holds them ("": float32 from a float32 start-up program), or
#: their bfloat16 rounding
WEIGHT_DTYPES = ("", "bfloat16")


def is_weight_matrix(name: str, shape) -> bool:
    """Whether a persistable variable of `models.transformer` is one of
    the matrices a bundle's `weight_dtype` rounds: the embedding table
    (which is the head where they are tied) and every projection
    (`*_w` of two dimensions or more: the mixers', the FFNs', the
    experts', the head's). The small parameters stay float32: norm gains
    and biases, a scan's vectors (`a_log`, `dt_b`, `d_skip`), a
    convolution's taps (`*_conv_w` [taps, channels]) and a router
    (`*_router_w`: it runs at the highest precision so that near ties
    fall as the reference's)."""
    if name == "tok_emb":
        return True
    return (name.endswith("_w") and len(shape) >= 2
            and not name.endswith(("_conv_w", "_router_w")))


def prefill_program(block, bound: int, *, vocab: int, n_layers: int,
                    d_model: int, n_heads: int, d_ff: int,
                    max_context: int):
    """The program of ONE prefill bucket as `export_decode_model` traces
    it: padded ids [batch, bound] and each row's true length in, the
    logits row of the prompt's last position, what every layer's cache
    holds of each token, the chosen experts and the selections out.
    Returns (main program, the fetch targets' names in that order)."""
    from . import Program as _Program
    from . import layers as _L
    from . import program_guard as _program_guard
    from .models import transformer as _tfm

    main, _startup = _Program(), _Program()
    kvs: List = []
    routes: List = []
    sels: List = []
    with _program_guard(main, _startup):
        src = _L.data("src_ids", [bound], dtype="int64")
        # the prompt's length: the head's one row is position n - 1
        # (a padding row of the batch has length 0: row 0)
        n_tokens = _L.data("n_tokens", [], dtype="int32")
        last = _L.elementwise_max(
            _L.elementwise_sub(n_tokens, _L.fill_constant(
                [1], "int32", 1.0)),
            _L.fill_constant([1], "int32", 0.0))
        logits = _tfm.transformer_lm(
            src, vocab, n_layers=n_layers, d_model=d_model,
            n_heads=n_heads, d_ff=d_ff, max_len=max_context,
            pos_table_len=max_context, collect_kv=kvs,
            collect_routes=routes, block=block,
            head_rows=_L.unsqueeze(last, [1]),
            collect_selected=sels if block.choosing_layers(n_layers)
            else None,
            n_tokens=n_tokens if "state" in block.cache_kinds(n_layers)
            else None)
        targets = [logits.name] + [v.name for rows in kvs
                                   for v in rows]
        if block.ffn == "moe_gated":
            targets.append(_L.stack(routes, axis=1).name)
        targets += [v.name for v in sels]
    return main, targets


def export_decode_model(dirname: str, model_cfg: Dict, *,
                        scope: Optional[Scope] = None,
                        length_buckets: Sequence[int] = (64, 128),
                        slots: Optional[int] = None,
                        block_size: Optional[int] = None,
                        pool_blocks: Optional[int] = None,
                        prefill_batch_size: int = 1,
                        eos_id: Optional[int] = None,
                        weight_dtype: str = "") -> str:
    """Export the autoregressive-decode bundle (serving/decode): PREFILL
    artifacts (one per length bucket, full causal attention over the
    prompt, fetching the logits row of the prompt's last position + what
    every layer's paged cache holds of each token, so the cache can be
    seeded) plus ONE fixed-shape DECODE-STEP artifact (one token per
    slot, reading/writing the paged pools through per-slot block
    tables). What the pools are is the block's to say
    (`BlockSpec.cache_pools`: per-head K and V, or one latent row) and
    is recorded under ``decode.cache``: the engine allocates, seeds,
    donates and describes what is declared there. A block with window
    layers declares two kinds of cache there (``layer_kinds``,
    ``window``, ``kinds``): the full layers' pools of ``pool_blocks``
    blocks and the window layers' of ``slots x (window / block_size + 1)
    + 1``, and the step takes a second table, ``window_tables``. A block
    with "conv" layers (gated short convolutions) declares a third kind
    there, ``state``: such a layer has no pool; the step takes and
    returns ``conv_state_{i}`` [slots, conv_taps - 1, d_model] in its
    pools' place, and a prefill artifact returns, in its K/V's place,
    the state a prompt of ``n_tokens`` leaves ([batch, conv_taps - 1,
    d_model]), which the admission writes into the sequence's slot. A
    "mamba" layer's state is two such arrays (``ssm_state_{i}`` [slots,
    ssm_state, ssm_inner], ``conv_state_{i}`` [slots, conv_taps - 1,
    ssm_inner]). A block with "cross" layers declares ``shared`` there:
    the layer whose pool they read (``source``) and the layers that read
    it through the full layers' table without owning a pool
    (``readers``); a "gmu" layer keeps nothing. Such a block's prefill
    artifacts run the layers behind the source's K/V on the ONE row the
    head is computed for (`models.transformer.transformer_lm`).

    A prefill artifact takes the prompt's true length beside the padded
    ids (``n_tokens`` [batch] int32) and computes the head for that one
    position: its ``logits`` are [batch, 1, vocab], never [batch, bound,
    vocab] (3 GB at a 128 k vocabulary and a 6 k bucket). Both are recorded in serving.json: the prefill side
    uses the exact bucket schema `export_serving_model` writes (so
    serving.ModelVersion serves it unchanged), and a ``decode`` section
    carries the pool geometry + feed/fetch specs of the step artifact.

    The weights are NOT inlined into the artifacts: every artifact takes
    them as its first argument (a name -> array dict) and the bundle
    carries them once, in ``weights.npz``. Inlined, each artifact of the
    full-width LM embedded all ~435 M parameters (1.74 GB apiece): the
    export took minutes and the load died at the host's memory limit
    before the first request. The loader puts the weights on the device
    once and every bucket and the decode step share them.

    Export on the platform that will serve: the kernel choice (Pallas on
    a TPU, the XLA references elsewhere) and the platform are fixed at
    trace time, so a bundle exported on a CPU host does not serve on the
    chip.

    model_cfg: the transformer_lm architecture — vocab_size, n_layers,
    d_model, n_heads, d_ff, max_context (the trained sequence length;
    sizes the shared pos_emb table and bounds every sequence's
    prompt+generated length), and optionally ``block``: a
    `models.transformer.BlockSpec` or its dict form, the GPT-2 block
    when absent. It is recorded, as a dict, under
    ``decode.model_cfg.block`` of serving.json. Weights are bound by
    NAME from `scope`: whatever persistable variables the three builders
    of `models.transformer` create for that block (tok_emb, pos_emb,
    attn{i}_*, ffn{i}_* or moe{i}_*, ln*_{i}_*, lm_head_*) — the names
    `transformer_lm` assigns in training.

    With experts in the block the step artifact carries the routing
    counters as one more feed and fetch after the pools (``moe_stats``
    [3] int32: pairs routed, experts touched, layer-steps, over live
    slots), named under ``decode.moe_stats``, and every artifact has
    one more fetch, the chosen experts of each layer and row (the step:
    ``moe_routes_out`` [n_layers, slots, top_k] int32; a prefill:
    ``moe_routes`` [batch, n_layers, bound, top_k]), named under
    ``decode.moe_routes``: what a check against a reference needs to
    tell a near-tie in the router from a fault. A dense model's
    artifacts have none of these. With a sparse-attention indexer in
    the block every artifact has one more fetch behind those, the
    positions attention was restricted to (the step: ``selected_out``
    [n_layers, slots, index_topk] int32, every slot's positions, -1
    behind its count; a prefill: ``selected_{i}`` [batch, bound,
    bound / 32] int32 a layer, every row's positions, one bit a
    position: 19 MB at a 6,144 bucket and four layers where positions
    would be 201), named under ``decode.selections``.

    slots / block_size / pool_blocks default from the PT_DECODE_MAX_SLOTS
    / PT_DECODE_BLOCK_SIZE / PT_DECODE_POOL_BLOCKS env knobs (8 / 16 /
    64). Block 0 of the pool is reserved as the null block; usable KV
    capacity is (pool_blocks - 1) * block_size tokens.

    weight_dtype: "" (the matrices as the scope holds them) | "bfloat16":
    the dtype the bundle STORES its matrices in
    (`is_weight_matrix`: the projections and the embedding table; the
    small parameters stay float32) and the server holds them in on the
    device. The artifacts are traced on the rounded matrices: the
    residual stream, the scans, the states, the pools and the logits
    stay float32, and each product multiplies float32 rows by the stored
    matrix as it is (XLA's TPU compiler reads a bfloat16 operand of a
    float32 product where it lies; no float32 copy is made). Recorded in
    serving.json under ``weights`` (``dtype``, ``bytes``, and ``stored``:
    the names whose ``.npy`` piece holds the bfloat16 bits as uint16,
    numpy having no bfloat16 of its own); a bundle without the key loads
    its pieces as they are. A block with experts is refused: the grouped
    expert matmuls' bfloat16 cases are not built.
    """
    import jax
    import jax.numpy as jnp
    from . import Program as _Program
    from . import program_guard as _program_guard
    from .core import lowering
    from .core.compat import jax_export
    from .models import transformer as _tfm

    from .serving.batcher import env_int as _env_int

    slots = slots or _env_int("PT_DECODE_MAX_SLOTS", 8)
    block_size = block_size or _env_int("PT_DECODE_BLOCK_SIZE", 16)
    pool_blocks = pool_blocks or _env_int("PT_DECODE_POOL_BLOCKS", 64)
    scope = scope or global_scope()
    cfg = dict(model_cfg)
    block = _tfm.BlockSpec.of(cfg.get("block"))
    if weight_dtype not in WEIGHT_DTYPES:
        raise ValueError(f"weight_dtype is one of {WEIGHT_DTYPES}, got "
                         f"{weight_dtype!r}")
    if weight_dtype == "bfloat16" and block.ffn == "moe_gated":
        raise NotImplementedError(
            "weight_dtype='bfloat16' with ffn='moe_gated': the grouped "
            "expert matmuls (XLA's and kernels/expert_matmul.py) are "
            "built for float32 matrices")
    vocab = int(cfg["vocab_size"])
    n_layers = int(cfg["n_layers"])
    d_model = int(cfg["d_model"])
    n_heads = int(cfg["n_heads"])
    d_ff = int(cfg["d_ff"])
    max_context = int(cfg["max_context"])
    if d_model % n_heads and not block.head_dim:
        raise ValueError(f"d_model {d_model} not divisible by n_heads "
                         f"{n_heads}")
    head_dim = block.head_width(n_heads, d_model)
    buckets = sorted(int(b) for b in length_buckets)
    if not buckets or buckets[-1] > max_context:
        raise ValueError(f"length_buckets {buckets} must be non-empty and "
                         f"bounded by max_context {max_context}")
    if pool_blocks < 2:
        raise ValueError("pool_blocks must be >= 2 (block 0 is the "
                         "reserved null block)")
    max_blocks_per_seq = -(-max_context // block_size)
    # window layers keep their own, bounded pool: a slot holds at most
    # the blocks its window reaches (window / block_size and the one the
    # edge crosses), whatever the context
    window_blocks_per_seq = window_pool_blocks = 0
    if block.window:
        if block.window % block_size:
            raise ValueError(f"window {block.window} is not whole blocks "
                             f"of {block_size}")
        window_blocks_per_seq = min(block.window // block_size + 1,
                                    max_blocks_per_seq)
        window_pool_blocks = slots * window_blocks_per_seq + 1
    kinds = block.cache_kinds(n_layers)

    def _bind_state(program):
        state = {}
        for var in program.list_vars():
            if var.persistable and scope.has_var(var.name):
                v = scope.find_var(var.name)
                if v is not None:
                    v = jnp.asarray(v)
                    if weight_dtype and is_weight_matrix(var.name, v.shape):
                        # a copy, unless the scope holds the matrix
                        # rounded already: then the array itself
                        v = v.astype(weight_dtype)
                    state[var.name] = v
        return state

    weights: Dict[str, object] = {}   # the bundle's one copy, by name

    def _trace(program, feed_names, target_names, shapes, dtypes,
               alt_shapes=None):
        """Trace+serialize one program with the weights as its first
        argument; returns (blob, out_avals, alt_avals, weight_names) —
        alt for batch_major ground truth on the prefill."""
        pruned = program.clone(for_test=True).prune(targets=target_names,
                                                    feeds=feed_names)
        state = _bind_state(pruned)
        weights.update(state)
        step, _ = lowering.build_step_fn(pruned, list(feed_names),
                                         list(target_names), [],
                                         is_test=True)
        key = jax.random.PRNGKey(0)

        def serve(state, *feeds):
            env = dict(zip(feed_names, feeds))
            fetches, _ = step(state, env, key)
            return fetches

        state_avals = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                       for n, v in state.items()}
        example = [jax.ShapeDtypeStruct(tuple(s), d)
                   for s, d in zip(shapes, dtypes)]
        exported = jax_export().export(jax.jit(serve))(state_avals,
                                                       *example)
        alt_avals = None
        if alt_shapes is not None:
            alt = [jax.ShapeDtypeStruct(tuple(s), d)
                   for s, d in zip(alt_shapes, dtypes)]
            try:
                alt_avals = list(jax.eval_shape(serve, state_avals, *alt))
            except Exception:
                alt_avals = None
        return (exported.serialize(), list(exported.out_avals), alt_avals,
                sorted(state))

    os.makedirs(dirname, exist_ok=True)
    from .core.types import device_dtype, np_dtype

    ids_dt = np_dtype(device_dtype("int64"))
    i32 = np_dtype(device_dtype("int32"))

    # -- prefill: one full-attention artifact per length bucket ----------
    cache = block.cache_pools(n_heads, d_model)
    blocks_of = {"full": pool_blocks, "window": window_pool_blocks}
    # every layer's memory as the step takes it: (feed stem, shape)
    layer_feeds = [_tfm.cache_feeds(block, i, n_heads, d_model, slots,
                                    block_size, blocks_of, max_context)
                   for i in range(n_layers)]
    kv_roles = [tuple(f"{stem.removesuffix('_cache')}_{i}"
                      for stem, _ in feeds)    # k_0, v_0 | latent_0 |
                for i, feeds in enumerate(layer_feeds)]    # conv_state_0
    fetch_roles = ["logits"] + [n for pair in kv_roles for n in pair]
    with_experts = block.ffn == "moe_gated"
    if with_experts:
        fetch_roles.append("moe_routes")
    with_indexer = block.index_topk > 0
    # the layers whose attention chooses what it reads: every layer of a
    # block with an indexer, and the layers whose mixer chooses blocks
    choosing = block.choosing_layers(n_layers)
    with_selection = bool(choosing)
    if block.page_rows and block_size != block.page_rows:
        raise ValueError(f"block_size {block_size} is not the selection's "
                         f"block {block.page_rows}: a page of the "
                         "pools is the block a query chooses")
    sparse = block.sparse_sizes
    selected_roles = [f"selected_{i}" for i in choosing]
    # a fetch a layer, not one stacked: the stack would be a second copy
    # of every layer's bits while the bucket's largest temporaries live
    fetch_roles += selected_roles
    # which form each bucket's attention takes where it is traced: the
    # kernel choice is fixed at export time (`attention_form`)
    from .kernels.flash_attention import attention_form
    score_width = (block.qk_nope_head_dim + block.qk_rope_head_dim
                   if block.attention == "latent" else head_dim)
    prefill_attention = {
        str(bound): attention_form(
            bound, bound, score_width,
            selected=(with_indexer and bound > block.index_topk)
            or (block.page_rows > 0 and bound >= sparse["dense_len"]
                and bound > sparse["topk"] * sparse["block"]))
        for bound in buckets}
    buckets_meta = []
    for bound in buckets:
        main, targets = prefill_program(
            block, bound, vocab=vocab, n_layers=n_layers, d_model=d_model,
            n_heads=n_heads, d_ff=d_ff, max_context=max_context)
        B = prefill_batch_size
        shapes = [(B, bound), (B,)]
        blob, out_avals, alt_avals, weight_names = _trace(
            main, ["src_ids", "n_tokens"], targets, shapes, [ids_dt, i32],
            alt_shapes=[(B + 1, bound), (B + 1,)])
        feeds_meta = [{"name": "src_ids", "shape": [B, bound],
                       "dtype": np.dtype(ids_dt).name,
                       "batch_major": True},
                      {"name": "n_tokens", "shape": [B],
                       "dtype": np.dtype(i32).name, "batch_major": True}]
        fetch_meta = []
        for j, (role, aval) in enumerate(zip(fetch_roles, out_avals)):
            bm = bool(aval.shape) and int(aval.shape[0]) == B
            if bm and alt_avals is not None:
                a = alt_avals[j].shape
                bm = bool(a) and int(a[0]) == B + 1
            fetch_meta.append({"name": role,
                               "shape": [int(s) for s in aval.shape],
                               "dtype": np.dtype(aval.dtype).name,
                               "batch_major": bm})
        fn = f"prefill_len{bound}.stablehlo"
        with open(os.path.join(dirname, fn), "wb") as f:
            f.write(blob)
        buckets_meta.append({"length": bound, "file": fn,
                             "feeds": feeds_meta, "fetches": fetch_meta,
                             "weights": weight_names})

    # -- the decode step: one fixed-shape artifact -----------------------
    main, _startup = _Program(), _Program()
    moe_stats: List = []
    moe_routes: List = []
    selected: List = []
    with _program_guard(main, _startup):
        dlogits, pool_outs, dec_feed_names = _tfm.transformer_decode_step(
            vocab, n_layers=n_layers, d_model=d_model, n_heads=n_heads,
            d_ff=d_ff, max_context=max_context, slots=slots,
            block_size=block_size, pool_blocks=pool_blocks,
            max_blocks_per_seq=max_blocks_per_seq, block=block,
            moe_stats_out=moe_stats, moe_routes_out=moe_routes,
            selected_out=selected, window_pool_blocks=window_pool_blocks)
    dec_targets = [dlogits.name] + [v.name for outs in pool_outs
                                    for v in outs]
    dec_fetch_roles = ["logits"] + [
        f"{stem}_out_{i}" for i, feeds in enumerate(layer_feeds)
        for stem, _ in feeds]
    dec_shapes = [(slots,), (slots,), (slots, max_blocks_per_seq)]
    dec_dtypes = [ids_dt, i32, i32]
    if block.window:    # the window layers' table, behind the full one
        dec_shapes.append((slots, max_blocks_per_seq))
        dec_dtypes.append(i32)
    for feeds in layer_feeds:
        dec_shapes += [tuple(shape) for _, shape in feeds]
        dec_dtypes += [np.float32] * len(feeds)
    # a program that holds a share of the experts counts the pairs that
    # fell on them beside the three counters every expert model has
    moe_fields = ["assignments", "experts_touched", "layer_steps"] + (
        ["held_pairs"] if block.experts_held else [])
    if with_experts:    # the routing counters ride behind the pools
        dec_targets += [moe_stats[0].name, moe_routes[0].name]
        dec_fetch_roles += ["moe_stats_out", "moe_routes_out"]
        dec_shapes.append((len(moe_fields),))
        dec_dtypes.append(i32)
    if with_selection:  # and the selected positions behind those
        dec_targets.append(selected[0].name)
        dec_fetch_roles.append("selected_out")
    dec_blob, dec_avals, _, dec_weight_names = _trace(
        main, dec_feed_names, dec_targets, dec_shapes, dec_dtypes)
    with open(os.path.join(dirname, "decode.stablehlo"), "wb") as f:
        f.write(dec_blob)
    # numpy has no bfloat16 of its own (a piece would be written as raw
    # bytes of no dtype): such a matrix is stored as its bits, uint16, and
    # named under `weights.stored` for the loader to view back
    stored = sorted(n for n, v in weights.items()
                    if v.dtype == jnp.bfloat16)
    np.savez(os.path.join(dirname, SERVING_WEIGHTS_FILENAME),
             **{n: (np.asarray(v).view(np.uint16) if n in stored
                    else np.asarray(v)) for n, v in weights.items()})
    matrix_dtypes = {str(v.dtype) for n, v in weights.items()
                     if is_weight_matrix(n, v.shape)}
    dec_feeds_meta = [
        {"name": n, "shape": [int(x) for x in s],
         "dtype": np.dtype(d).name}
        for n, s, d in zip(dec_feed_names, dec_shapes, dec_dtypes)]
    dec_fetch_meta = [
        {"name": role, "shape": [int(x) for x in aval.shape],
         "dtype": np.dtype(aval.dtype).name}
        for role, aval in zip(dec_fetch_roles, dec_avals)]

    base = buckets_meta[-1]
    meta = {
        "feeds": base["feeds"], "fetch_names": fetch_roles,
        "fetches": base["fetches"], "batch_size": prefill_batch_size,
        "buckets": buckets_meta, "var_dims": {"src_ids": [1]},
        "weights_file": SERVING_WEIGHTS_FILENAME,
        # what the matrices are stored and served in (one dtype, else
        # "mixed"), the bytes of ALL the weights as stored, and the
        # pieces that hold bfloat16 bits as uint16
        "weights": {
            "dtype": (matrix_dtypes.pop() if len(matrix_dtypes) == 1
                      else "mixed"),
            "bytes": int(sum(int(v.size) * v.dtype.itemsize
                             for v in weights.values())),
            "stored": {"bfloat16_as_uint16": stored}},
        "decode": {
            "file": "decode.stablehlo", "weights": dec_weight_names,
            "feeds": dec_feeds_meta, "fetches": dec_fetch_meta,
            "slots": slots, "block_size": block_size,
            "pool_blocks": pool_blocks,
            "max_blocks_per_seq": max_blocks_per_seq,
            "max_context": max_context, "n_layers": n_layers,
            "n_heads": n_heads, "head_dim": head_dim,
            "vocab_size": vocab, "eos_id": eos_id,
            # what a paged cache holds of a token, a layer: the pools'
            # rows, the floats of them that carry the token, and the
            # bytes a token takes over all layers as the pools store it
            "cache": {
                "kind": cache["kind"],
                "rows": [list(row) for _, row in cache["pools"]],
                "row_floats": cache["row_floats"],
                "bytes_per_token": 4 * sum(
                    k in ("full", "window") for k in kinds)
                * sum(int(np.prod(row)) for _, row in cache["pools"])},
            "prefill_roles": {"logits": "logits",
                              "kv": [list(p) for p in kv_roles]},
            "prefill_attention": prefill_attention,
            "model_cfg": {"vocab_size": vocab, "n_layers": n_layers,
                          "d_model": d_model, "n_heads": n_heads,
                          "d_ff": d_ff, "max_context": max_context,
                          "block": block.to_dict()},
        },
    }
    if with_experts:
        meta["decode"]["moe_routes"] = {"fetch": "moe_routes_out",
                                        "prefill": "moe_routes"}
        meta["decode"]["moe_stats"] = {
            "feed": "moe_stats", "fetch": "moe_stats_out",
            "fields": moe_fields,
            # the most one step can add to a field: the engine folds the
            # device's int32 counters into host integers before they
            # could wrap
            "max_per_step": n_layers * max(
                slots * block.experts_per_tok, block.num_experts)}
    if block.window:
        # two kinds of cache, each with block ids of its own: what each
        # kind's layers hold of a token, how many blocks its pools have
        # and how many of them a slot can hold at once
        row_bytes = 4 * sum(int(np.prod(row)) for _, row in cache["pools"])
        meta["decode"]["cache"].update(
            layer_kinds=kinds, window=block.window,
            kinds={kind: {"layers": kinds.count(kind),
                          "pool_blocks": n_blocks,
                          "blocks_per_seq": per_seq,
                          "bytes_per_token": row_bytes * kinds.count(kind)}
                   for kind, n_blocks, per_seq in (
                       ("full", pool_blocks, max_blocks_per_seq),
                       ("window", window_pool_blocks,
                        window_blocks_per_seq))})
    if "state" in kinds:
        # a third kind, which is no cache: what a state layer remembers
        # of a SEQUENCE, a slot of the step's state arrays each
        at = kinds.index("state")
        rows = [list(r) for _, r in block.cache_pools(
            n_heads, d_model, at)["state"]]
        meta["decode"]["cache"]["layer_kinds"] = kinds
        meta["decode"]["cache"].setdefault("kinds", {
            "full": {"layers": kinds.count("full"),
                     "pool_blocks": pool_blocks,
                     "blocks_per_seq": max_blocks_per_seq,
                     "bytes_per_token": meta["decode"]["cache"][
                         "bytes_per_token"]}})
        meta["decode"]["cache"]["kinds"]["state"] = {
            "layers": kinds.count("state"), "rows": rows,
            "bytes_per_slot": 4 * kinds.count("state") * sum(
                int(np.prod(r)) for r in rows)}
    if "shared" in kinds:
        # a fourth fact: layers that read a pool they do not own (through
        # the full layers' table, no pool and no feed of their own)
        readers = [i for i, kind in enumerate(kinds) if kind == "shared"]
        meta["decode"]["cache"]["layer_kinds"] = kinds
        meta["decode"]["cache"]["shared"] = {
            "source": block.layer(readers[0]).kv_source,
            "readers": readers}
    if block.page_rows:
        # a layer of pools AND a state: the sequence's pooled keys ride
        # behind each such layer's pools, a slot's rows each
        at = choosing[0]
        rows = [list(r) for _, r in block.cache_pools(
            n_heads, d_model, at, max_context)["state"]]
        meta["decode"]["cache"]["pooled"] = {
            "layers": len(choosing), "rows": rows,
            "bytes_per_slot": 4 * len(choosing) * sum(
                int(np.prod(r)) for r in rows)}
    if with_selection:
        meta["decode"]["selections"] = {"fetch": "selected_out",
                                        "prefill": selected_roles,
                                        "topk": block.index_topk}
        if not with_indexer:    # whole blocks, chosen on pooled keys
            meta["decode"]["selections"]["blocks"] = dict(
                sparse, layers=choosing)
    with open(os.path.join(dirname, "serving.json"), "w") as f:
        json.dump(meta, f)
    return dirname


def load_serving_model(dirname: str):
    """Load an AOT artifact: returns (predict_fn, feed_names,
    fetch_names); predict_fn(*arrays) runs the compiled StableHLO."""
    import jax

    with open(os.path.join(dirname, "serving.json")) as f:
        meta = json.load(f)
    from .core.compat import jax_export
    with open(os.path.join(dirname, "serving.stablehlo"), "rb") as f:
        exported = jax_export().deserialize(bytearray(f.read()))

    def predict(*arrays):
        return exported.call(*arrays)

    return predict, [m["name"] for m in meta["feeds"]], meta["fetch_names"]


# ---------------------------------------------------------------------------
# checkpoint subsystem (io.py:466-735): serial dirs, _SUCCESS, keep-last-N
# ---------------------------------------------------------------------------

def _serial_dir(checkpoint_dir: str, serial: int) -> str:
    return os.path.join(checkpoint_dir, f"{CHECKPOINT_PREFIX}_{serial}")


def _committed_serials(checkpoint_dir: str) -> List[int]:
    out = []
    for name in os.listdir(checkpoint_dir):
        m = re.fullmatch(rf"{CHECKPOINT_PREFIX}_(\d+)", name)
        if m and os.path.exists(os.path.join(checkpoint_dir, name,
                                             SUCCESS_MARK_FILENAME)):
            out.append(int(m.group(1)))
    return sorted(out, reverse=True)


def get_latest_checkpoint_serial(checkpoint_dir: str,
                                 verify: Optional[bool] = None) -> int:
    """Newest committed serial — by default (PT_CKPT_VERIFY, on) the
    newest that also passes manifest verification. A committed serial
    that fails verification is QUARANTINED (renamed to
    ``checkpoint_N.corrupt``, never deleted — resilience/manifest.py) and
    the scan falls back to the next older one, so auto-resume restores
    the newest checkpoint that is actually restorable instead of
    faithfully loading garbage. Pre-manifest serials verify as legacy
    and are accepted."""
    if not checkpoint_dir or not os.path.isdir(checkpoint_dir):
        return -1
    if verify is None:
        verify = _verify_on_load()
    for serial in _committed_serials(checkpoint_dir):
        if not verify:
            return serial
        cur = _serial_dir(checkpoint_dir, serial)
        import warnings
        try:
            status, problems = _manifest.verify_dir(cur,
                                                    SUCCESS_MARK_FILENAME)
        except FileNotFoundError as e:
            # a peer rank quarantined (renamed) the dir mid-digest: the
            # serial is gone — skip it WITHOUT quarantining (nothing left
            # to rename). Any other OSError propagates: a transient EIO
            # must fail the load loudly, never rename a good serial away.
            warnings.warn(
                f"checkpoint serial {serial} in {checkpoint_dir!r} "
                f"vanished during verification ({e}) — a peer process "
                "quarantined it; falling back to the next older serial",
                stacklevel=2)
            continue
        if status != "corrupt":
            return serial
        try:
            dest = _manifest.quarantine(cur)
        except OSError:
            # multi-process load: another rank quarantined it first
            dest = "(already quarantined by a peer)"
        warnings.warn(
            f"checkpoint serial {serial} in {checkpoint_dir!r} failed "
            f"manifest verification ({'; '.join(problems[:3])}"
            f"{'...' if len(problems) > 3 else ''}) — quarantined to "
            f"{dest}; falling back to the next older serial",
            stacklevel=2)
    return -1


#: the subset of a PlacementPlan a checkpoint records as its plan stamp:
#: everything needed to decide "can this state restore onto THAT mesh
#: as-is, and if not, how to reshard it" — and nothing else (predictions,
#: collectives, costs are re-derived by the planner on the new topology)
PLAN_STAMP_KEYS = ("mesh", "specs", "zero", "sp_mode", "batch",
                   "devices_used", "program_fingerprint",
                   "calibration_version")


def plan_stamp(plan: Optional[dict]) -> Optional[dict]:
    """Project a plan dict down to the fields a checkpoint stamps into
    its manifest (PLAN_STAMP_KEYS). None in, None out."""
    if not plan:
        return None
    return {k: plan[k] for k in PLAN_STAMP_KEYS if k in plan}


def read_plan_stamp(checkpoint_dir: str,
                    serial: Optional[int] = None) -> Optional[dict]:
    """The plan stamp recorded in a committed checkpoint's manifest, or
    None (unstamped / pre-elastic / legacy checkpoint). `serial=None`
    reads the newest committed serial."""
    if serial is None:
        serial = get_latest_checkpoint_serial(checkpoint_dir, verify=False)
    if serial < 0:
        return None
    man = _manifest.read_manifest(_serial_dir(checkpoint_dir, serial))
    if not man:
        return None
    stamp = man.get("plan_stamp")
    return stamp if isinstance(stamp, dict) else None


class PlanMismatchError(IOError):
    """The checkpoint's plan stamp does not match the mesh/specs it is
    being restored onto, and the caller did not opt into resharding.
    Restoring dp-sharded (ZeRO) state onto a different mesh without a
    reshard silently loads wrong optimizer slices — refuse loudly."""


def check_plan_stamp(stamp: Optional[dict],
                     expect_plan: Optional[dict]) -> List[str]:
    """Mismatches between a checkpoint's plan stamp and the plan it is
    about to be restored under. Empty list = compatible as-is. An
    unstamped checkpoint or no expectation checks nothing (legacy
    acceptance — same contract as manifest 'legacy')."""
    if not stamp or not expect_plan:
        return []
    problems: List[str] = []
    for key in ("mesh", "specs", "zero", "sp_mode"):
        a, b = stamp.get(key), expect_plan.get(key)
        if a is not None and b is not None and a != b:
            problems.append(f"plan_stamp.{key}: checkpoint {a!r} != "
                            f"target {b!r}")
    return problems


def save_checkpoint(executor=None, checkpoint_dir: str = "", trainer_id: int = 0,
                    trainer_args: Optional[dict] = None, main_program=None,
                    max_num_checkpoints: int = 3, scope=None, plan=None):
    """io.py:466: write serial dir, then _SUCCESS marker, then scroll old.

    Multi-host safe (≙ each pserver checkpointing only its own shard,
    go/pserver/service.go:346): process 0 picks the serial and broadcasts
    it (ranks reading _SUCCESS markers themselves could diverge — only
    rank 0 writes markers), clears any uncommitted leftovers at that
    serial, all ranks barrier, every process writes just its addressable
    shards via save_persistables, all ranks barrier again, and only
    process 0 commits the _SUCCESS marker and scrolls old serials — a
    half-written multi-host checkpoint is never marked live, and a crashed
    attempt's files can never blend into the next one."""
    import jax
    multi = jax.process_count() > 1
    # serial picking must not re-digest (or quarantine) old serials on
    # every save — corruption handling is the LOAD path's duty
    serial = get_latest_checkpoint_serial(checkpoint_dir, verify=False) + 1
    if multi:
        from jax.experimental import multihost_utils
        serial = int(multihost_utils.broadcast_one_to_all(
            np.int32(serial)))
        cur = _serial_dir(checkpoint_dir, serial)
        if jax.process_index() == 0 and os.path.isdir(cur):
            shutil.rmtree(cur, ignore_errors=True)  # uncommitted leftovers
        multihost_utils.sync_global_devices(f"paddle_tpu_ckpt_pre_{serial}")
    cur = _serial_dir(checkpoint_dir, serial)
    if not multi and os.path.isdir(cur):
        # serial picking skips uncommitted dirs, so anything here is a
        # crashed attempt's leftovers — clear them, or stale files from a
        # different var set would blend into this save's manifest
        shutil.rmtree(cur, ignore_errors=True)
    os.makedirs(cur, exist_ok=True)
    save_persistables(executor, cur, main_program, scope=scope)
    if trainer_args:
        with open(os.path.join(cur, f"trainer_{trainer_id}.json"), "w") as f:
            json.dump(trainer_args, f)
    if multi:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(f"paddle_tpu_ckpt_{serial}")
    if not multi or jax.process_index() == 0:
        # manifest BEFORE _SUCCESS (every rank's files are on disk — the
        # barrier above guarantees it): a crash anywhere in this window
        # leaves an uncommitted dir the next save clears, never a
        # _SUCCESS-marked serial that cannot be verified
        stamp = plan_stamp(plan)
        _manifest.write_manifest(
            cur, layout="checkpoint",
            extra={"plan_stamp": stamp} if stamp else None)
        faults.crash_point("commit_crash")
        marker = os.path.join(cur, SUCCESS_MARK_FILENAME)
        tmp = marker + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(_manifest.success_payload(cur))
        os.replace(tmp, marker)
        _scroll_delete(checkpoint_dir, max_num_checkpoints)
    return serial


def load_checkpoint(executor=None, checkpoint_dir: str = "", serial: Optional[int] = None,
                    main_program=None, trainer_id: int = 0, scope=None,
                    verify: Optional[bool] = None,
                    expect_plan: Optional[dict] = None,
                    reshard: bool = False):
    """io.py:504: restore persistables (+ trainer args if present).

    `verify=False` skips manifest re-verification of an explicit serial —
    for callers that just selected it via the verifying
    get_latest_checkpoint_serial (re-digesting a multi-GB checkpoint
    doubles resume I/O for nothing).

    `expect_plan` declares the PlacementPlan the restored state is about
    to run under. If the checkpoint is plan-stamped and the stamp
    disagrees (mesh axes / per-var specs / zero / sp_mode), the load
    raises PlanMismatchError — unless `reshard=True`, the elastic path's
    opt-in: full host arrays load fine here, and the caller (the elastic
    supervisor / ParallelExecutor(plan=...)) rescatters them onto the new
    mesh. Unstamped checkpoints check nothing (legacy acceptance)."""
    if serial is None:
        # verified selection: quarantines corrupt serials, falls back to
        # the newest one that verifies
        serial = get_latest_checkpoint_serial(checkpoint_dir)
    elif _verify_on_load() if verify is None else verify:
        # an EXPLICIT serial is a user decision — no silent fallback;
        # corruption raises (and the dir is left in place for forensics)
        status, problems = _manifest.verify_dir(
            _serial_dir(checkpoint_dir, serial), SUCCESS_MARK_FILENAME)
        if status == "corrupt":
            raise CheckpointCorruptError(
                f"checkpoint serial {serial} in {checkpoint_dir!r} failed "
                f"manifest verification: {'; '.join(problems[:5])}")
    if serial < 0:
        return None
    cur = _serial_dir(checkpoint_dir, serial)
    if expect_plan is not None and not reshard:
        problems = check_plan_stamp(
            read_plan_stamp(checkpoint_dir, serial), expect_plan)
        if problems:
            raise PlanMismatchError(
                f"checkpoint serial {serial} in {checkpoint_dir!r} was "
                f"written under a different plan: "
                f"{'; '.join(problems[:5])} — pass reshard=True (or use "
                "resilience.elastic / tools/reshard.py) to restore onto "
                "the new mesh")
    retry_call(load_persistables, executor, cur, main_program, scope=scope,
               policy=_LOAD_RETRY)
    args_path = os.path.join(cur, f"trainer_{trainer_id}.json")
    if os.path.exists(args_path):
        with open(args_path) as f:
            return json.load(f)
    return None


def clean_checkpoint(checkpoint_dir: str, delete_dir: bool = False):
    _scroll_delete(checkpoint_dir, max_num_checkpoints=0)
    if delete_dir and os.path.isdir(checkpoint_dir) and not os.listdir(checkpoint_dir):
        os.rmdir(checkpoint_dir)


def _scroll_delete(checkpoint_dir: str, max_num_checkpoints: int):
    if not os.path.isdir(checkpoint_dir):
        return
    serials = []
    for name in os.listdir(checkpoint_dir):
        m = re.fullmatch(rf"{CHECKPOINT_PREFIX}_(\d+)", name)
        if m:
            serials.append(int(m.group(1)))
    serials.sort(reverse=True)
    for s in serials[max_num_checkpoints:]:
        shutil.rmtree(_serial_dir(checkpoint_dir, s), ignore_errors=True)


def _is_checkpoint_var(var) -> bool:
    """≙ io.py:_is_checkpoint_var — persistable, but not gradients or
    feed/fetch plumbing (a trainer checkpoints model+optimizer state
    only)."""
    name = var.name
    if not _is_persistable(var):
        return False
    return "@GRAD" not in name and name not in ("feed", "fetch")


def save_persist_vars_without_grad(executor, dirname, program,
                                   filename=None, scope=None):
    """≙ io.py save_persist_vars_without_grad (io.py:545 area): the
    distributed-checkpoint flavor of save_persistables — every
    persistable except gradient buffers."""
    return save_vars(executor, dirname, main_program=program,
                     predicate=_is_checkpoint_var, filename=filename,
                     scope=scope)


def load_persist_vars_without_grad(executor, dirname, program,
                                   has_model_dir=False, filename=None,
                                   scope=None):
    """≙ io.py load_persist_vars_without_grad:545 (has_model_dir: the
    checkpoint layout keeps model vars under <dir>/__model__-era
    subdirectory in the reference; here serial dirs already separate,
    so it selects the same directory)."""
    return load_vars(executor, dirname, main_program=program,
                     predicate=_is_checkpoint_var, filename=filename,
                     scope=scope)
