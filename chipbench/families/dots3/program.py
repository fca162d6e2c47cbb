"""The ``dots3`` family as a user of the system constructs it. The only
module of the family that imports ``mxnet_tpu``; what is the same for
every model (the engine, the feed, the counters) is ``chipbench/program.py``.
"""
from __future__ import annotations

from mxnet_tpu.gluon.model_zoo.dots3 import Dots3Model
from mxnet_tpu.ndarray.ndarray import NDArray

from chipbench.families.dots3 import weights as W

#: counters of the program that count a trace or a compile of a generation
#: program: more than zero of them inside a window fails the run
TRACE_COUNTERS = ("model.dots3.trace", "ops.sampling.trace")

#: keys of the ``model`` group that are the constructor's own
_KEYS = (
    "vocab_size", "hidden_size", "layer_types", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
    "sliding_window_size", "swa_num_attention_heads", "swa_q_lora_rank",
    "swa_kv_lora_rank", "swa_qk_nope_head_dim", "swa_qk_rope_head_dim",
    "swa_v_head_dim", "intermediate_size", "moe_intermediate_size",
    "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
    "rope_theta", "swa_rope_theta", "rms_norm_eps")


def build_model(model, seed):
    """``Dots3Model`` from the configuration's keys, told which experts
    it holds, its parameters installed from the benchmark's seeded
    weights a layer at a time, each leaf in the dtype the model declares
    (no float32 copy of a layer lies beside the bfloat16 one)."""
    s = W.sizes(model)
    net = Dots3Model(
        n_routed_experts=s["E_all"],
        experts_held=range(s["E_lo"], s["E_lo"] + s["E_held"]),
        **{k: model[k] for k in _KEYS})
    install(net, W.make(model, seed))
    return net


def install(net, weights, for_program=True):
    """The seeded ``weights`` into ``net``'s parameters, the top and then
    a layer at a time, the way ``load_parameters`` installs a checkpoint.
    ``for_program=False`` installs the reference's float32 arrays (a
    float32 model, for tests)."""
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
