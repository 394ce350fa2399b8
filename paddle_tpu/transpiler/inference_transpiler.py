"""Inference transpiler: fold BatchNorm into the preceding conv.

≙ reference transpiler/inference_transpiler.py (240 LoC: _fuse_batch_norm
walks conv2d→batch_norm pairs, folds the affine transform into conv
weights/bias, deletes the bn op, adjusts downstream input names). Same
rewrite here — program ops are edited and the folded weights are written
back into the SCOPE (the weights are data, exactly like the reference
mutating the vars in the inference scope).

Math: for y = BN(conv(x, W) + b) with saved mean m, var v, scale g,
shift beta:  a = g / sqrt(v + eps);  W' = W * a (per out-channel);
b' = (b - m) * a + beta  — so BN becomes a bias add.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..core.program import OpDesc, Program, default_main_program, unique_name
from ..core.scope import Scope, global_scope


class InferenceTranspiler:
    """t = InferenceTranspiler(); t.transpile(program, scope=scope)"""

    def transpile(self, program: Optional[Program] = None,
                  place=None, scope: Optional[Scope] = None) -> Program:
        """Apply to an INFERENCE program (clone(for_test=True).prune(...)
        or load_inference_model's result). Folding mutates the weights in
        `scope`; a program that still trains would corrupt them."""
        program = program if program is not None else default_main_program()
        scope = scope or global_scope()
        if any(op.type == "autodiff" for op in program.global_block.ops):
            raise ValueError(
                "InferenceTranspiler needs an inference program; this one "
                "still contains training ops (clone(for_test=True)."
                "prune([target]) first)")
        self._fuse_batch_norm(program, scope)
        program.invalidate_cache()
        return program

    def _fuse_batch_norm(self, program: Program, scope: Scope):
        block = program.global_block
        ops = block.ops
        new_ops = []
        i = 0
        while i < len(ops):
            op = ops[i]
            fused = None
            consumed = 0
            if op.type == "conv2d":
                # pattern: conv2d [-> elementwise_add bias] -> batch_norm
                bias_op = None
                j = i + 1
                if (j < len(ops) and ops[j].type == "elementwise_add"
                        and ops[j].inputs["X"][0] == op.outputs["Output"][0]
                        and self._is_bias(block, ops[j].inputs["Y"][0])):
                    bias_op = ops[j]
                    j += 1
                if (j < len(ops) and ops[j].type == "batch_norm"
                        and ops[j].attrs.get("is_test", False)
                        and ops[j].inputs["X"][0] == (
                            bias_op.outputs["Out"][0] if bias_op
                            else op.outputs["Output"][0])):
                    # the pre-BN intermediate must have no reader outside
                    # the fused chain (a residual branch reading it would
                    # dangle after the rewrite)
                    chain = [o for o in (op, bias_op, ops[j]) if o]
                    pre_bn = (bias_op.outputs["Out"][0] if bias_op
                              else op.outputs["Output"][0])
                    outside = any(
                        pre_bn in other.input_names()
                        for other in ops if other not in chain)
                    if not outside:
                        fused = self._fold(block, scope, op, bias_op, ops[j])
                        consumed = j - i + 1
            if fused is not None:
                new_ops.extend(fused)
                i += consumed
            else:
                new_ops.append(op)
                i += 1
        block.ops = new_ops

    @staticmethod
    def _is_bias(block, name) -> bool:
        try:
            v = block.var(name)
        except KeyError:
            return False
        return v.is_parameter and len(v.shape) == 1

    def _fold(self, block, scope, conv: OpDesc, bias_op, bn: OpDesc):
        w_name = conv.inputs["Filter"][0]
        w = scope.find_var(w_name)
        scale = scope.find_var(bn.inputs["Scale"][0])
        shift = scope.find_var(bn.inputs["Bias"][0])
        mean = scope.find_var(bn.inputs["Mean"][0])
        var = scope.find_var(bn.inputs["Variance"][0])
        if any(v is None for v in (w, scale, shift, mean, var)):
            return None  # weights not materialized — leave the pair alone
        eps = float(bn.attrs.get("epsilon", 1e-5))
        w = np.asarray(w, np.float64)
        a = np.asarray(scale, np.float64) / np.sqrt(
            np.asarray(var, np.float64) + eps)
        scope.set_var(w_name, (w * a[:, None, None, None]).astype(np.float32))
        b0 = 0.0
        if bias_op is not None:
            b0 = np.asarray(scope.find_var(bias_op.inputs["Y"][0]),
                            np.float64)
        bias = (b0 - np.asarray(mean, np.float64)) * a \
            + np.asarray(shift, np.float64)

        bias_name = unique_name(f"{w_name}.bnfold_bias")
        block.create_var(bias_name, shape=(len(bias),), dtype="float32",
                         persistable=True)
        scope.set_var(bias_name, bias.astype(np.float32))

        # conv keeps its op (weights updated in place); bias add + BN fold
        # into ONE bias add writing BN's output name so downstream readers
        # are untouched. A relu fused into the BN op (fuse_with_relu,
        # layers.batch_norm(act="relu")) must survive the fold: emit it
        # as an explicit op after the bias add.
        out_name = bn.outputs["Y"][0]
        if bn.attrs.get("fuse_with_relu"):
            mid = unique_name(f"{out_name}.bnfold_pre_relu")
            block.create_var(mid, shape=block.var(out_name).shape,
                             dtype=block.var(out_name).dtype)
            add = OpDesc("elementwise_add",
                         {"X": [conv.outputs["Output"][0]],
                          "Y": [bias_name]},
                         {"Out": [mid]}, {"axis": 1})
            relu = OpDesc("relu", {"X": [mid]}, {"Out": [out_name]}, {})
            return [conv, add, relu]
        add = OpDesc("elementwise_add",
                     {"X": [conv.outputs["Output"][0]], "Y": [bias_name]},
                     {"Out": [out_name]}, {"axis": 1})
        return [conv, add]


class Float16Transpiler:
    """Low-precision inference transpiler.

    ≙ reference paddle/contrib/float16/float16_transpiler.py:21-72: that
    one casts the saved weights to fp16, rewrites kernels to fp16, and
    inserts cast ops around feed/fetch. The TPU reading: weights in the
    scope are cast to bfloat16 (the TPU's fast half type — halves weight
    HBM), the program's vars are re-typed, and `amp_dtype` is set so the
    whole forward computes in bf16; the executor's per-op dtype
    harmonization plays the reference's boundary cast ops (any f32 feed
    is cast down where it meets a bf16 weight, results come back f32 at
    the fetch if the final op is f32 — no graph surgery needed).
    """

    #: per-op input slots whose vars stay f32 (normalization statistics —
    #: cast stats would shift the normalized distribution)
    _KEEP_SLOTS = {"batch_norm": ("Mean", "Variance")}

    def _stat_names(self, program: Program):
        keep = set()
        for block in program.blocks:
            for op in block.ops:
                for slot in self._KEEP_SLOTS.get(op.type, ()):
                    keep.update(op.input(slot))
        return keep

    def transpile(self, program: Optional[Program] = None,
                  scope: Optional[Scope] = None,
                  dtype: str = "bfloat16"):
        import ml_dtypes
        if dtype not in ("bfloat16", "float16"):
            raise ValueError(
                f"Float16Transpiler: dtype must be 'bfloat16' or 'float16', "
                f"got {dtype!r}")
        program = program or default_main_program()
        scope = scope or global_scope()
        if any(op.type == "autodiff" for op in program.global_block.ops):
            raise ValueError(
                "Float16Transpiler needs an inference program (it would "
                "quantize the f32 master weights a training program "
                "updates); clone(for_test=True).prune([target]) first")
        target = ml_dtypes.bfloat16 if dtype == "bfloat16" else np.float16
        keep = self._stat_names(program)
        for block in program.blocks:
            for var in block.vars.values():
                if not var.persistable or var.dtype != "float32":
                    continue
                if var.name in keep:
                    continue
                val = scope.find_var(var.name)
                if val is None:
                    continue
                scope.set_var(var.name, np.asarray(val).astype(target))
                var.dtype = dtype
        program.amp_dtype = dtype
        program.invalidate_cache()
        return program
