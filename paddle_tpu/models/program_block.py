"""What the served decoders' program builders share, whatever the model:
one op appended by slot names, a named output, and the block's parameters
by name with the norm, the projection and the SwiGLU every one of them
has, and for those with several residual streams (models/motif3.py,
models/xing4.py) the path around a sublayer. models/afmoe.py, kimi_k2.py
and falcon_h1.py each subclass `Block` with their own layers, pools and
heads."""

from __future__ import annotations

from typing import List

from .. import layers
from ..layer_helper import LayerHelper


def named_out(name, dtype="float32"):
    from ..core.ir import default_main_program

    return default_main_program().current_block().create_var(
        name=name, dtype=dtype, stop_gradient=True)


def op(type_, ins, outs, attrs=None, dtype="float32"):
    """Append one op; `outs` maps slot -> a Variable, or None for a fresh
    temporary. Returns the outputs in the order of `outs`."""
    helper = LayerHelper(type_)
    made = [v if v is not None
            else helper.create_variable_for_type_inference(dtype)
            for v in outs.values()]
    helper.append_op(type_, {k: [v] for k, v in ins.items()},
                     {k: [v] for k, v in zip(outs, made)}, attrs or {})
    return made[0] if len(made) == 1 else made


def seeded_params(specs, init_std, seed: int):
    """Deterministic parameters for tests and demos, as numpy arrays in the
    dtypes `specs` states (name -> (shape, kind, dtype)): a ``normal`` kind
    at ``init_std(name, shape)``, a ``(mean, std)`` kind as that normal
    draw, any other the constant that fills it; drawn in the names' order."""
    import ml_dtypes
    import numpy as np

    rng = np.random.RandomState(seed)
    out = {}
    for name, (shape, kind, dtype) in sorted(specs.items()):
        if kind == "normal":
            v = rng.normal(0.0, init_std(name, shape), shape)
        elif isinstance(kind, tuple):
            v = rng.normal(kind[0], kind[1], shape)
        else:
            v = np.full(shape, kind)
        out[name] = v.astype(ml_dtypes.bfloat16 if dtype == "bfloat16"
                             else dtype)
    return out


class Block:
    """The layers of one program: parameters by name (`specs`: name ->
    (shape, kind, dtype)), the norms, the projections and the SwiGLU, which
    are the same in every phase; how a layer attends is the phase's own."""

    def __init__(self, cfg, kv, specs):
        self.cfg, self.kv, self.specs = cfg, kv, specs
        self.pool_outs: List[str] = []
        self.counts = None          # running sum of the MoE layers' Counts

    def param(self, name):
        shape, _kind, dtype = self.specs[name]
        return layers.static_data(name, list(shape), dtype)

    def arrays(self, names, shapes, dtypes):
        """Fed pool or state arrays and the variables they are written back
        under (``<name>_out``, which join `pool_outs`)."""
        ins = [layers.static_data(n, list(s), d)
               for n, s, d in zip(names, shapes, dtypes)]
        outs = [named_out(n + "_out", d) for n, d in zip(names, dtypes)]
        self.pool_outs += [o.name for o in outs]
        return ins, outs

    def pools(self, i):
        """(PoolK, PoolV), (PoolKOut, PoolVOut) of layer i, whose K and V of
        ``num_kv_heads x head_dim`` lie in a context's pages."""
        from ..serving.kv_cache import pool_array_names

        cfg, pool = self.cfg, self.kv.context
        shape = [pool.num_pages, pool.page_size,
                 cfg.num_kv_heads * cfg.head_dim]
        return self.arrays(pool_array_names(i, False), [shape, shape],
                            [cfg.dtype, cfg.dtype])

    def norm(self, x, name):
        return op("rms_norm", {"X": x, "Scale": self.param(name)},
                  {"Y": None}, {"epsilon": self.cfg.rms_norm_eps})

    def linear(self, x, name, **attrs):
        return op("linear_acc32", {"X": x, "W": self.param(name)},
                  {"Out": None}, attrs)

    def swiglu(self, x, p, w1, w3, w2):
        mid = op("swiglu", {"Gate": self.linear(x, p + w1),
                            "Up": self.linear(x, p + w3)}, {"Out": None})
        return self.linear(mid, p + w2)

    def mhc_attrs(self):
        """(attrs of `mhc_pre`, attrs of `mhc_post`) of a model whose
        residual is several streams."""
        raise NotImplementedError

    def streams_in(self, xs, p):
        """`mhc_pre` with the maps' parameters that start with `p`: (u,
        the sublayer's input before its norm; the three maps)."""
        return op(
            "mhc_pre",
            {"X": xs, "Gamma": self.param(p + "norm"),
             "Phi": self.param(p + "phi"), "Scale": self.param(p + "scale"),
             "Bias": self.param(p + "bias")}, {"U": None, "Maps": None},
            self.mhc_attrs()[0])

    def around(self, xs, p, norm, sublayer):
        """One sublayer inside the streams' residual path."""
        u, maps = self.streams_in(xs, p)
        y = sublayer(self.norm(u, norm))
        return op("mhc_post", {"X": xs, "Y": y, "Maps": maps},
                  {"Out": None}, self.mhc_attrs()[1])
