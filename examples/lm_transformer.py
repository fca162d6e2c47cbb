"""Causal transformer language model with flash attention and optional
ring-attention sequence parallelism.

Beyond-reference long-context showcase: the reference's sequence story
tops out at fused RNNs (src/operator/rnn-inl.h); here attention runs as
a Pallas flash kernel and, over a dp×sp mesh, as ring attention
(shard_map + ppermute over 'sp') so sequence length scales across
chips. Run on the 8-device virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python examples/lm_transformer.py --sp 4
"""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, np, npx, parallel
from mxnet_tpu.gluon import nn


class CausalSelfAttention(nn.HybridBlock):
    def __init__(self, dim, heads, sp_axis=None):
        super().__init__()
        self.heads = heads
        self.sp_axis = sp_axis
        self.qkv = nn.Dense(3 * dim, use_bias=False, flatten=False)
        self.proj = nn.Dense(dim, use_bias=False, flatten=False)

    def forward(self, x):
        B, S, D = x.shape
        H = self.heads
        qkv = self.qkv(x).reshape(B, S, 3, H, D // H)
        q = qkv[:, :, 0].transpose(0, 2, 1, 3)
        k = qkv[:, :, 1].transpose(0, 2, 1, 3)
        v = qkv[:, :, 2].transpose(0, 2, 1, 3)
        if self.sp_axis:
            out = npx.ring_attention(q, k, v, causal=True,
                                     axis_name=self.sp_axis)
        else:
            out = npx.flash_attention(q, k, v, causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, D)
        return self.proj(out)


class Block(nn.HybridBlock):
    def __init__(self, dim, heads, sp_axis=None):
        super().__init__()
        self.ln1 = nn.LayerNorm()
        self.attn = CausalSelfAttention(dim, heads, sp_axis)
        self.ln2 = nn.LayerNorm()
        self.mlp1 = nn.Dense(4 * dim, activation="relu", flatten=False)
        self.mlp2 = nn.Dense(dim, flatten=False)

    def forward(self, x):
        x = x + self.attn(self.ln1(x))
        return x + self.mlp2(self.mlp1(self.ln2(x)))


class TinyLM(nn.HybridBlock):
    def __init__(self, vocab, dim=64, heads=4, depth=2, sp_axis=None):
        super().__init__()
        self.emb = nn.Embedding(vocab, dim)
        self.blocks = nn.HybridSequential()
        for _ in range(depth):
            self.blocks.add(Block(dim, heads, sp_axis))
        self.head = nn.Dense(vocab, flatten=False)

    def forward(self, tokens):
        return self.head(self.blocks(self.emb(tokens)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--sp", type=int, default=0,
                    help="sequence-parallel degree (0 = single chip "
                         "flash attention)")
    args = ap.parse_args()

    import jax
    vocab, batch = 64, 4
    sp_axis = None
    mesh = None
    if args.sp > 1:
        n_dev = jax.local_device_count()
        dp = max(1, n_dev // args.sp)
        mesh = parallel.make_mesh((dp, args.sp), ("dp", "sp"))
        parallel.set_mesh(mesh)
        sp_axis = "sp"

    net = TinyLM(vocab, sp_axis=sp_axis)
    net.initialize(mx.init.Xavier())

    rng = onp.random.RandomState(0)
    toks = rng.randint(0, vocab, (batch, args.seq_len + 1))

    if sp_axis:
        from jax.sharding import PartitionSpec as P
        step = parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            optimizer_params={"learning_rate": 1e-3}, mesh=mesh,
            batch_axis="dp")
        data = np.array(toks[:, :-1])
        label = np.array(toks[:, 1:].astype("int32"))
        # materialize deferred params BEFORE sharding the tokens:
        # deferred init runs eagerly on first use, and eager ops
        # cannot mix mesh-sharded and single-device operands
        net.infer_shape(data)
        # shard sequence over 'sp' by hand: (B, S) -> P('dp', 'sp')
        import jax as _jax
        from jax.sharding import NamedSharding
        sh = NamedSharding(mesh, P("dp", "sp"))
        data._install(_jax.device_put(data._data, sh))
        label._install(_jax.device_put(label._data, sh))
        losses = [float(step(data, label).asnumpy())
                  for _ in range(args.steps)]
    else:
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 1e-3})
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        data = np.array(toks[:, :-1])
        label = np.array(toks[:, 1:].astype("int32"))
        losses = []
        for _ in range(args.steps):
            with autograd.record():
                out = net(data)
                loss = loss_fn(out.reshape(-1, vocab),
                               label.reshape(-1)).mean()
            loss.backward()
            trainer.step(1)
            losses.append(float(loss.asnumpy()))

    print(f"seq_len={args.seq_len} sp={args.sp or 1}: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
