"""The one-token families beside the block-diffusion path: a one-token
engine never enters the path."""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.serving import GenerationEngine  # noqa: E402


def test_the_one_token_tick_path_is_untouched():
    """A one-token family's engine never enters the block-diffusion path:
    its tick in flight is finished by the plain tick's commit, and each
    tick is one dispatch and one sync as before."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
    np.random.seed(42)
    mx.np.random.seed(42)
    net = gpt_small(vocab_size=64, units=32, num_layers=2, num_heads=4,
                    max_length=128)
    net.initialize(mx.init.Xavier())
    with GenerationEngine(net, max_slots=2, max_length=64, paged=True,
                          page_size=8) as eng:
        assert eng._blockdiff is None
        assert eng._finish_ahead == eng._finish_tick

        def refuse(*_a, **_k):
            raise AssertionError("the block-diffusion path was entered")
        eng._denoise_tick = eng._finish_denoise = eng._enter_denoise = refuse
        names = ("serving.generate.dispatches", "serving.generate.host_syncs",
                 "serving.generate.tokens", "serving.generate.denoise_steps")
        c0 = {n: telemetry.counter_value(n) for n in names}
        res = eng.submit(np.arange(5, dtype=np.int32) + 3,
                         max_new_tokens=9).result(timeout=300)
        eng._drain_ahead()
        got = {n: telemetry.counter_value(n) - c0[n] for n in names}
    assert len(res.tokens) == 9
    # the first token comes from prefill; each other from one tick
    assert got["serving.generate.tokens"] == 9
    assert got["serving.generate.dispatches"] \
        == got["serving.generate.host_syncs"] == 8
    assert got["serving.generate.denoise_steps"] == 0
