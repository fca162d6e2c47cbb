"""GPT-2 as a user of the system constructs it. The only module of the
family that imports ``mxnet_tpu``; what is the same for every model (the
engine, the train step, the feed, the counters) is ``chipbench/program.py``.
"""
from __future__ import annotations

from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
from mxnet_tpu.ndarray.ndarray import NDArray

from chipbench.families.gpt2 import weights as W

#: counters of the program that count a trace or a compile of a generation
#: program: more than zero of them inside a window fails the run
TRACE_COUNTERS = ("model.gpt.trace", "ops.sampling.trace")


def build_model(model, seed):
    """``GPTModel`` at the sizes of ``model`` (a GPT-2 ``config.json``
    group), its parameters installed from the benchmark's seeded weights
    the way ``load_parameters`` installs a checkpoint."""
    s = W.sizes(model)
    net = GPTModel(vocab_size=s["V"], units=s["D"], num_layers=s["L"],
                   num_heads=s["H"], hidden_size=s["F"],
                   max_length=s["P"],
                   dropout=float(model.get("resid_pdrop", 0.0)))
    stacked = W.make(model, seed)
    leaves = W.program_leaves(stacked)
    del stacked
    params = net.collect_params()
    missing = set(params) ^ set(leaves)
    if missing:
        raise SystemExit(f"chipbench: parameter names differ: "
                         f"{sorted(missing)[:6]}")
    for name, p in params.items():
        p.set_data(NDArray(leaves[name]))
    return net
