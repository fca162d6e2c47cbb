"""The ``phi4flash`` family as a user of the system constructs it. The
only module of the family that imports ``mxnet_tpu``; what is the same
for every model (the engine, the feed, the counters) is
``chipbench/program.py``.
"""
from __future__ import annotations

import jax.numpy as jnp

from mxnet_tpu.gluon.model_zoo.phi4flash import Phi4FlashModel
from mxnet_tpu.ndarray.ndarray import NDArray

from chipbench.families.phi4flash import weights as W

#: counters of the program that count a trace or a compile of a generation
#: program: more than zero of them inside a window fails the run
TRACE_COUNTERS = ("model.phi4flash.trace", "ops.sampling.trace")

#: keys of the ``model`` group that are the constructor's own
_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "sliding_window",
    "mb_per_layer", "layer_norm_eps", "d_state", "d_conv", "expand",
    "dt_rank", "prefill_chunk")

def build_model(model, seed, **more):
    """``Phi4FlashModel`` from the configuration's keys, its parameters
    installed from the benchmark's seeded weights, each leaf in the dtype
    the model declares."""
    net = Phi4FlashModel(**{k: model[k] for k in _KEYS if k in model},
                         **more)
    install(net, W.make(model, seed),
            for_program=more.get("dtype", "bfloat16") == "bfloat16")
    return net


def install(net, weights, for_program=True):
    """The seeded ``weights`` into ``net``'s parameters: the top, then
    group by group, a stacked group's leaves stacked from its layers' a
    leaf at a time (the layers of a group are made, their leaves stacked
    and set, and the layers dropped). ``for_program=False`` installs the
    reference's float32 arrays (a float32 model, for tests)."""
    s = weights.s
    params = net.collect_params()
    seen = set()

    def put(name, a):
        params[name].set_data(NDArray(a))
        seen.add(name)

    for name, a in weights.top(for_program).items():
        put(name, a)
    for group, layers in W.group_layers(s).items():
        made = [weights.layer(i, for_program) for i in layers]
        for name in made[0]:
            put(f"{group}_{name}", made[0][name]
                if group.startswith("mid_")
                else jnp.stack([m[name] for m in made]))
        del made
    if seen != set(params):
        raise SystemExit(f"chipbench: parameter names differ: "
                         f"{sorted(seen ^ set(params))[:6]}")
