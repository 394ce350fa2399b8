"""Parameter initializers as startup-program ops.

≙ reference python/paddle/fluid/initializer.py: each initializer appends an
init op (fill_constant / uniform_random / gaussian_random) writing the
persistable parameter in the *startup* program — initialization is itself a
program, run once by the executor, exactly like the reference.
"""

from __future__ import annotations

import math

from .core.program import Block, VarDesc


class Initializer:
    def __call__(self, var: VarDesc, block: Block):
        raise NotImplementedError


class ConstantInitializer(Initializer):
    def __init__(self, value: float = 0.0):
        self.value = value

    def __call__(self, var, block):
        block.append_op("fill_constant", {}, {"Out": var.name},
                        {"shape": list(var.shape), "dtype": var.dtype,
                         "value": float(self.value)})


class UniformInitializer(Initializer):
    def __init__(self, low: float = -1.0, high: float = 1.0, seed: int = 0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        block.append_op("uniform_random", {}, {"Out": var.name},
                        {"shape": list(var.shape), "dtype": var.dtype,
                         "min": self.low, "max": self.high, "seed": self.seed})


class NormalInitializer(Initializer):
    def __init__(self, loc: float = 0.0, scale: float = 1.0, seed: int = 0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op("gaussian_random", {}, {"Out": var.name},
                        {"shape": list(var.shape), "dtype": var.dtype,
                         "mean": self.loc, "std": self.scale, "seed": self.seed})


class NumpyArrayInitializer(Initializer):
    """≙ reference NumpyArrayInitializer: init from a literal array via the
    assign_value op."""

    def __init__(self, value):
        import numpy as np
        self.value = np.asarray(value)

    def __call__(self, var, block):
        if tuple(var.shape) and tuple(self.value.shape) != tuple(var.shape):
            raise ValueError(
                f"NumpyArrayInitializer for {var.name}: value shape "
                f"{self.value.shape} != parameter shape {var.shape}")
        block.append_op("assign_value", {}, {"Out": var.name},
                        {"shape": list(self.value.shape), "dtype": var.dtype,
                         "values": self.value.reshape(-1).tolist()})


class PaddedInitializer(Initializer):
    """`inner` over the leading `shape` of a parameter that is STORED
    wider (a last dimension in whole lane tiles), zeros behind it: the
    draw is the unpadded parameter's, made in the startup program and
    padded there."""

    def __init__(self, inner: Initializer, shape):
        self.inner, self.shape = inner, tuple(int(n) for n in shape)

    def __call__(self, var, block):
        if self.shape == tuple(var.shape):
            return self.inner(var, block)
        drawn = block.create_var(name=var.name + "@unpadded",
                                 shape=self.shape, dtype=var.dtype)
        self.inner(drawn, block)
        block.append_op(
            "pad", {"X": drawn.name}, {"Out": var.name},
            {"paddings": [p for have, want in zip(self.shape, var.shape)
                          for p in (0, int(want) - have)],
             "pad_value": 0.0})


class TruncatedNormalInitializer(Initializer):
    def __init__(self, loc: float = 0.0, scale: float = 1.0, seed: int = 0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op("truncated_gaussian_random", {}, {"Out": var.name},
                        {"shape": list(var.shape), "dtype": var.dtype,
                         "mean": self.loc, "std": self.scale, "seed": self.seed})


def _fan_in_out(var: VarDesc):
    shape = var.shape
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    receptive = 1
    for s in shape[2:]:
        receptive *= s
    return shape[1] * receptive, shape[0] * receptive


class XavierInitializer(Initializer):
    """Glorot init (initializer.py XavierInitializer)."""

    def __init__(self, uniform: bool = True, fan_in=None, fan_out=None, seed: int = 0):
        self.uniform, self.fan_in, self.fan_out, self.seed = uniform, fan_in, fan_out, seed

    def __call__(self, var, block):
        fi, fo = _fan_in_out(var)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        if self.uniform:
            limit = math.sqrt(6.0 / (fi + fo))
            UniformInitializer(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / (fi + fo))
            NormalInitializer(0.0, std, self.seed)(var, block)


class MSRAInitializer(Initializer):
    """Kaiming/He init (initializer.py MSRAInitializer)."""

    def __init__(self, uniform: bool = True, fan_in=None, seed: int = 0):
        self.uniform, self.fan_in, self.seed = uniform, fan_in, seed

    def __call__(self, var, block):
        fi, _ = _fan_in_out(var)
        fi = self.fan_in if self.fan_in is not None else fi
        if self.uniform:
            limit = math.sqrt(6.0 / fi)
            UniformInitializer(-limit, limit, self.seed)(var, block)
        else:
            NormalInitializer(0.0, math.sqrt(2.0 / fi), self.seed)(var, block)


class BilinearInitializer(Initializer):
    """Bilinear upsampling kernel init for conv_transpose (initializer.py)."""

    def __call__(self, var, block):
        import numpy as np
        shape = var.shape
        f = math.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        weight = np.zeros(shape, dtype="float32")
        size = shape[2] * shape[3]
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            idx = np.unravel_index(i, shape)
            weight[idx] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        block.append_op("assign_value", {}, {"Out": var.name},
                        {"shape": list(shape), "dtype": var.dtype,
                         "values": weight.ravel().tolist()})


# Aliases matching the reference's public names
Constant = ConstantInitializer
Uniform = UniformInitializer
Normal = NormalInitializer
TruncatedNormal = TruncatedNormalInitializer
Xavier = XavierInitializer
MSRA = MSRAInitializer
Bilinear = BilinearInitializer


_force_init_on_cpu = False


def force_init_on_cpu() -> bool:
    """≙ initializer.py force_init_on_cpu flag. On this runtime XLA owns
    placement — initializer ops run wherever the startup program is
    dispatched — so the flag is recorded for API parity and read by
    nothing (the reference used it to keep large inits off the GPU)."""
    return _force_init_on_cpu


class init_on_cpu:
    """≙ initializer.py init_on_cpu() context guard (API parity; see
    force_init_on_cpu)."""

    def __enter__(self):
        global _force_init_on_cpu
        self._prev = _force_init_on_cpu
        _force_init_on_cpu = True
        return self

    def __exit__(self, *exc):
        global _force_init_on_cpu
        _force_init_on_cpu = self._prev
        return False
