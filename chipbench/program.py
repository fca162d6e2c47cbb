"""The system under test, as the benchmark sees it.

The one file of ``chipbench/`` that imports ``mxnet_tpu``. It goes through
the entry points a user calls (``GPTModel``, ``GenerationEngine.submit`` ->
``GenerationStream``, ``TrainStep.__call__``, ``mx.np.array``) and takes
from the program only its spans, counters and kernel names. The yardstick
(traffic, reference, costs, trace reduction, ``correct``) lives beside it
and imports nothing from here but these functions.
"""
from __future__ import annotations

import gc

import jax

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, gluon, parallel, telemetry
from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.serving import GenerationEngine

from chipbench import weights as W

#: counters of the program that count a trace or a compile of a generation
#: program: more than zero of them inside a window fails the run
TRACE_COUNTERS = ("model.gpt.trace", "ops.sampling.trace")
#: the counters the serving reducers read
SERVE_COUNTERS = tuple("serving.generate." + n for n in (
    "dispatches", "host_syncs", "tokens", "prefill_chunks", "prefills"))


def configure_compile_cache():
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else the fixed directory
    inside the checkout. Returns the directory."""
    return compile_cache.configure(compile_cache.CHECKOUT_DIR)


def counters(names):
    return {n: telemetry.counter_value(n) for n in names}


def traces():
    return sum(telemetry.counter_value(c) for c in TRACE_COUNTERS)


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


def build_engine(net, serve_args):
    """The engine as the configuration's ``serve`` group states it, warmed
    up: every program the traffic can reach compiles here, in set-up."""
    return GenerationEngine(net, **serve_args).warmup()


def build_train_step(net, train_args):
    """Next-token cross entropy over (B, T, V) logits and (B, T) labels:
    the loss keeps the row axis (one mean per sequence), which is what
    ``TrainStep`` masks and averages over. (``chip_smoke.LmLoss`` flattens
    rows into tokens, and ``TrainStep`` then keeps the first B *tokens*.)"""
    opt = dict(train_args["optimizer_params"])
    return parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), train_args["optimizer"],
        opt, compute_dtype=train_args["compute_dtype"])


def feed(tokens):
    """One batch through the normal feed: host ids -> ``mx.np.array``."""
    return mx.np.array(tokens[:, :-1]), mx.np.array(tokens[:, 1:])


def step_leaf_names(step):
    """Names of the leaves the step updates, in the order of its optimizer
    state (one entry per leaf once the step has run)."""
    return [n for n, p in step.net.collect_params().items()
            if p.grad_req != "null"]


def step_first_moments(step):
    """Adam's first moment of every leaf, as the step holds it."""
    return [s[0] for s in step._opt_states]


def step_params(step):
    return {n: p.data()._data
            for n, p in step.net.collect_params().items()}


def loss_value(loss):
    return float(loss.asnumpy())


def release():
    """Give the device back: dead arrays and loaded executables."""
    gc.collect()
    jax.clear_caches()
    gc.collect()
