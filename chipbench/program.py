"""The system under test, as the benchmark sees it: what is the same for
every model.

With a family's own ``families/<name>/program.py`` (which builds the model
through the class a user would construct) the one file of ``chipbench/``
that imports ``mxnet_tpu``. It goes through the entry points a user calls
(``GenerationEngine.submit`` -> ``GenerationStream``, ``TrainStep.__call__``,
``mx.np.array``) and takes from the program only its spans, counters and
kernel names. The yardstick (traffic, reference, costs, trace reduction,
``correct``) lives beside it and imports nothing from here but these
functions.
"""
from __future__ import annotations

import gc

import jax

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, gluon, parallel, telemetry
from mxnet_tpu.serving import GenerationEngine

#: the counters the serving reducers read
SERVE_COUNTERS = tuple("serving.generate." + n for n in (
    "dispatches", "host_syncs", "tokens", "prefill_chunks", "prefills"))


def configure_compile_cache():
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else the fixed directory
    inside the checkout. Returns the directory."""
    return compile_cache.configure(compile_cache.CHECKOUT_DIR)


def counters(names):
    return {n: telemetry.counter_value(n) for n in names}


def traces(names):
    """How often the program traced or compiled a generation program, by
    the family's ``TRACE_COUNTERS``: more than zero of them inside a
    window fails the run."""
    return sum(counters(names).values())


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
