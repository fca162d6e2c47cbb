"""The ``sdar`` family as a user of the system constructs it. The only
module of the family that imports ``mxnet_tpu``; what is the same for
every model (the engine, the feed, the counters) is ``chipbench/program.py``.
"""
from __future__ import annotations

from mxnet_tpu.gluon.model_zoo.sdar import SDARModel
from mxnet_tpu.ndarray.ndarray import NDArray

from chipbench.families.sdar import weights as W

#: counters of the program that count a trace or a compile of a generation
#: program: more than zero of them inside a window fails the run
TRACE_COUNTERS = ("model.sdar.trace", "ops.sampling.trace")

#: keys of the ``model`` group that are the constructor's own
_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_intermediate_size",
    "num_experts", "num_experts_per_tok", "norm_topk_prob", "rope_theta",
    "rms_norm_eps", "block_length", "denoise_steps", "mask_token_id",
    "remasking_strategy")


def build_model(model, seed, **more):
    """``SDARModel`` from the configuration's keys, its parameters
    installed from the benchmark's seeded weights a layer at a time, each
    leaf in the dtype the model declares (no float32 copy of a layer lies
    beside the bfloat16 one)."""
    net = SDARModel(**{k: model[k] for k in _KEYS}, **more)
    install(net, W.make(model, seed),
            for_program=more.get("dtype", "bfloat16") == "bfloat16")
    return net


def install(net, weights, for_program=True):
    """The seeded ``weights`` into ``net``'s parameters, the top and then
    a layer at a time, the way ``load_parameters`` installs a checkpoint.
    ``for_program=False`` installs the reference's float32 arrays (a
    float32 model, for tests and the chip check)."""
    params = net.collect_params()
    seen = set()
    parts = [("", weights.top)] + [
        (f"layers_{i}_", lambda low, i=i: weights.layer(i, low))
        for i in range(weights.s["L"])]
    for prefix, make in parts:
        for name, a in make(for_program).items():
            params[prefix + name].set_data(NDArray(a))
            seen.add(prefix + name)
    if seen != set(params):
        raise SystemExit(f"chipbench: parameter names differ: "
                         f"{sorted(seen ^ set(params))[:6]}")
