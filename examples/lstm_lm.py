"""Word-level LSTM language model (BASELINE.json config 3).

The reference's headline RNN workload is example/rnn's PTB LSTM LM on
the cuDNN fused path (src/operator/rnn-inl.h). Here the same model
shape runs on the fused scan LSTM (gluon.rnn.LSTM lowers to ONE
lax.scan over the sequence — the TPU-native equivalent of the cuDNN
multi-layer kernel), trained with truncated BPTT, optional hybridized
bulk steps, and perplexity reporting.

Data: a deterministic synthetic corpus with PTB-like statistics
(Zipfian unigrams + a short-range bigram structure the model can
learn), so the example is runnable offline; point --text at any
whitespace-tokenized file (e.g. real PTB) to train on it instead.

Run (CPU smoke):
    JAX_PLATFORMS=cpu python examples/lstm_lm.py --steps 8
"""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse
import math
import time

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, np
from mxnet_tpu.gluon import nn, rnn


class LSTMLanguageModel(nn.HybridBlock):
    """Embedding -> multi-layer fused LSTM -> tied-capacity decoder
    (reference shape: example/rnn/word_lm model.py)."""

    def __init__(self, vocab, embed=200, hidden=200, layers=2,
                 dropout=0.2):
        super().__init__()
        self.embed = nn.Embedding(vocab, embed)
        self.drop = nn.Dropout(dropout)
        self.lstm = rnn.LSTM(hidden, num_layers=layers,
                             dropout=dropout, layout="NTC",
                             input_size=embed)
        self.decoder = nn.Dense(vocab, flatten=False)
        self._hidden, self._layers = hidden, layers

    def begin_state(self, batch_size, ctx=None):
        return self.lstm.begin_state(batch_size=batch_size, ctx=ctx)

    def forward(self, tokens, state):
        x = self.drop(self.embed(tokens))
        out, new_state = self.lstm(x, state)
        return self.decoder(self.drop(out)), new_state


def synthetic_corpus(n_tokens, vocab, seed=0):
    """Zipf unigrams + deterministic bigram successor structure:
    token t is followed by (t*7+3)%vocab 60% of the time, so a
    learning model's perplexity drops well below the unigram floor."""
    rng = onp.random.RandomState(seed)
    ranks = onp.arange(1, vocab + 1, dtype="f8")
    p = (1.0 / ranks) / (1.0 / ranks).sum()
    toks = onp.empty(n_tokens, "i4")
    toks[0] = 0
    zipf = rng.choice(vocab, size=n_tokens, p=p)
    follow = rng.uniform(size=n_tokens) < 0.6
    for i in range(1, n_tokens):
        toks[i] = (toks[i - 1] * 7 + 3) % vocab if follow[i] \
            else zipf[i]
    return toks


def batchify(tokens, batch):
    n = len(tokens) // batch
    return tokens[:n * batch].reshape(batch, n)


def detach(state):
    return [s.detach() for s in state]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--text", help="whitespace-tokenized corpus file")
    ap.add_argument("--vocab", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--bptt", type=int, default=32)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3.0)
    ap.add_argument("--clip", type=float, default=0.25)
    ap.add_argument("--hybridize", action="store_true")
    args = ap.parse_args()

    if args.text:
        words = open(args.text).read().split()
        uniq = sorted(set(words))[:args.vocab - 1]
        idx = {w: i + 1 for i, w in enumerate(uniq)}
        toks = onp.array([idx.get(w, 0) for w in words], "i4")
    else:
        toks = synthetic_corpus(50_000, args.vocab)

    data = batchify(toks, args.batch)
    net = LSTMLanguageModel(args.vocab, embed=args.hidden,
                            hidden=args.hidden, layers=args.layers)
    net.initialize(mx.init.Xavier())
    if args.hybridize:
        net.hybridize()

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    state = net.begin_state(args.batch)

    n_batches = (data.shape[1] - 1) // args.bptt
    if n_batches < 1:
        raise SystemExit(
            f"corpus too small: need at least batch*(bptt+1) = "
            f"{args.batch * (args.bptt + 1)} tokens for "
            f"--batch {args.batch} --bptt {args.bptt}")
    t0 = time.time()
    tokens_seen = 0
    ppl = None
    for step in range(args.steps):
        off = (step % n_batches) * args.bptt
        x = np.array(data[:, off:off + args.bptt])
        y = np.array(data[:, off + 1:off + args.bptt + 1]
                     .astype("i4"))
        state = detach(state)  # truncated BPTT boundary
        with autograd.record():
            logits, state = net(x, state)
            loss = loss_fn(logits, y).mean()
        loss.backward()
        grads = [p.grad() for p in net.collect_params().values()
                 if p.grad_req != "null"]
        gluon.utils.clip_global_norm(grads, args.clip)
        trainer.step(1)
        tokens_seen += args.batch * args.bptt
        ppl = math.exp(min(float(loss.asnumpy()), 20.0))
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}  ppl {ppl:.1f}")
    wps = tokens_seen / (time.time() - t0)
    print(f"final_ppl {ppl:.2f}  tokens_per_sec {wps:.0f}")
    # the bigram structure is learnable: perplexity must end below
    # the vocab-size random floor
    assert ppl < args.vocab, "no learning signal"


if __name__ == "__main__":
    main()
