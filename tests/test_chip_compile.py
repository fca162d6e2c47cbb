"""The main path's Pallas kernels, and paged decode attention (which
has none), compiled for a DESCRIBED v5e at GPT-2 large shapes (20
heads x 64, 1280 units, MLP 5120, vocabulary 50257, 1024 positions,
page 16, 8 slots); and the paged decode and chunk PROGRAMS of a
two-layer ``GPTModel`` at GPT-2 large and medium widths, whose text
says which layout the chip gives the KV pools, whether a program
copies one, and whether the tick splits a gathered view into heads.

Interpret-mode parity tests cannot see what the chip's compiler
refuses: a block shape off the (8, 128) tiling, a scalar operand in
VMEM, a block larger than the scoped VMEM limit. These compiles can,
at no chip time (on-chip-measurement guide, section 2): the installed
TPU compiler targets a ``v5e:2x2`` that is described, not attached.
Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module-scoped fixture — never at
import, in a skipif or in parametrize: only one process may hold the
TPU library, and every xdist worker imports this file. All such tests
stay in this one file for the same reason.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import quantized as qz

B, H, D, S_MAX, PAGE = 8, 20, 64, 1024, 16
P_MAX = S_MAX // PAGE
N_PAGES = B * P_MAX + 1
SPEC_K = 4


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A described-device executable can be written to the persistent
    cache but not read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, kernel=True):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == kernel
    return text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("seq", [16, 32, 200, 1024])
def test_flash_forward(one_chip, no_compile_cache, dtype, seq):
    """The engine's paged prefill buckets (16, 32), an unaligned
    length (block-padded) and the full training sequence."""
    qkv = ((2, H, seq, D), dtype)
    _compile(lambda q, k, v: att.flash_attention_pallas(
        q, k, v, causal=True), one_chip, qkv, qkv, qkv)


_KV = {"bf16": jnp.bfloat16, "fp32": jnp.float32, "int8": jnp.int8}


def _scales(kind, shape):
    return [(shape, jnp.float32)] * 2 if kind == "int8" else []


@pytest.mark.parametrize("kind", list(_KV))
@pytest.mark.parametrize("sq", [1, SPEC_K + 1], ids=["decode", "verify"])
def test_dense_decode(one_chip, no_compile_cache, kind, sq):
    qdt = jnp.float32 if kind == "int8" else _KV[kind]
    kv = ((B, H, S_MAX, D), _KV[kind])

    def fn(q, k, v, lens, *sc):
        return att.decode_attention_pallas(
            q, k, v, lens, k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None)

    _compile(fn, one_chip, ((B, H, sq, D), qdt), kv, kv,
             ((B,), jnp.int32), *_scales(kind, (B, H)))


@pytest.mark.parametrize("kind", list(_KV))
@pytest.mark.parametrize("sq", [1, SPEC_K + 1], ids=["decode", "verify"])
@pytest.mark.parametrize("heads", [H, 16], ids=["large", "medium"])
def test_paged_decode(one_chip, no_compile_cache, kind, sq, heads):
    """GPT-2 large's 20 heads and GPT-2 medium's 16. Paged decode is
    the compiler's own gather + masked softmax: no kernel of ours is
    in the program. ``sq == 1`` (the tick) takes the rows reader,
    ``rows_decode_attention`` over ``gather_rows``, an int8 pool with
    its scales on scores and probabilities; ``sq > 1`` (a verify) takes
    the gathered reader, ``gather_kv`` split into heads, an int8 pool
    dequantized to a float32 view."""
    qdt = jnp.float32 if kind == "int8" else _KV[kind]
    pool = ((N_PAGES, PAGE, heads * D), _KV[kind])

    def fn(q, k, v, table, lens, *sc):
        return att.paged_decode_attention(
            q, k, v, table, lens, k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None)

    text = _compile(fn, one_chip, ((B, heads, sq, D), qdt), pool, pool,
                    ((B, P_MAX), jnp.int32), ((B,), jnp.int32),
                    *_scales(kind, (N_PAGES, heads)), kernel=False)
    # the tick never splits a view into heads; a verify does
    assert (f"[{B},{S_MAX},{heads},{D}]" in text) == (sq > 1)


# -- the paged programs themselves: where the pools lie ------------------
_HLO_DTYPE = {"bf16": "bf16", "fp32": "f32", "int8": "s8"}
CHUNK = 32


_PAGED_PROGRAMS = {}


def _paged_program(sharding, heads, kind, role):
    """``gpt_paged_<role>`` of a two-layer GPTModel ``heads`` x 64 wide
    as the benchmark's engine runs it (bf16 compute, page 16, 8 slots,
    1024 positions, chunks of 32; a small vocabulary, which no pool
    sees), compiled for the described chip. Returns the compiled text
    and the pool's shape as that text prints it. Several tests read one
    program's text: each is compiled once a process."""
    if (heads, kind, role) not in _PAGED_PROGRAMS:
        _PAGED_PROGRAMS[heads, kind, role] = _compile_paged_program(
            sharding, heads, kind, role)
    return _PAGED_PROGRAMS[heads, kind, role]


def _compile_paged_program(sharding, heads, kind, role):
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.random_state import next_key
    net = GPTModel(512, units=heads * D, num_layers=2, num_heads=heads,
                   max_length=S_MAX)
    net.initialize()
    net.cast_compute_params("bfloat16")
    cache = net.init_paged_cache(B, N_PAGES, PAGE, S_MAX,
                                 dtype=_KV[kind])
    progs = net._ensure_paged()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    rows, args = {
        "decode": (B, (i32(B), i32(B))),
        "chunk": (1, (i32(1, CHUNK), i32(), i32(), i32(), i32(P_MAX))),
    }[role]
    args = (next_key(), net._param_call_datas(progs["params"]),
            net._quant_arg(), net._lora_arg(), net._lora_idx(None, rows),
            *args, cache)
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), args)
    text = progs[role].lower(*avals).compile().as_text()
    pool = cache["k"][0]
    return text, "%s[%s]" % (_HLO_DTYPE[kind],
                             ",".join(map(str, pool.shape)))


@pytest.mark.parametrize("role", ["decode", "chunk"])
@pytest.mark.parametrize("heads", [H, 16], ids=["large", "medium"])
def test_paged_program_copies_no_pool(one_chip, no_compile_cache, heads,
                                      role):
    """The chip keeps a bf16 pool ``(n_pages, page_size, H * 64)``
    row-major (its rows are whole (8, 128) tiles), so the scatter into
    it, the gather from it and the donated result all run on the layout
    the argument came in: the program holds no ``copy`` of a pool's
    shape. On ``(n_pages, H, page_size, 64)`` the same programs held
    four a layer, two thirds of a decode tick on the chip (PERF.md §6,
    PR 28). Metadata of a compile, not a chip reading."""
    text, pool = _paged_program(one_chip, heads, "bf16", role)
    layouts = set(re.findall(re.escape(pool) + r"\{([\d,]*)", text))
    assert layouts == {"2,1,0"}, layouts
    # the four pools are parameters of the entry and alias its results
    entry = text[text.index("ENTRY "):]
    assert len(re.findall(re.escape(pool) + r"\S* parameter\(", entry)) \
        == 4
    assert text.count("may-alias") >= 4
    copies = re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text)
    assert pool not in copies, copies


@pytest.mark.parametrize("kind", list(_KV))
@pytest.mark.parametrize("heads", [H, 16], ids=["large", "medium"])
def test_paged_tick_splits_no_view_into_heads(one_chip, no_compile_cache,
                                              heads, kind):
    """The decode tick attends the gathered rows as they lie
    (``rows_decode_attention``): the compiled program holds no operation
    (reshape, copy or fusion) whose result is ``[8,1024,H,64]``, the
    re-tiling of a view from ``H * 64``-wide rows to 64-wide heads that
    was the largest device operation of a tick (5.06 of 12.3 ms at 20
    heads, 4.05 of 9.5 at 16: PERF.md §6, PR 32), nor its transpose, and
    an int8 pool no float32 view. The chunk program still splits its
    one slot's view (``[1,1024,H,64]``). Metadata of a compile, not a
    chip reading."""
    text, _pool = _paged_program(one_chip, heads, kind, "decode")
    results = re.findall(r"= \(?(\w+\[[\d,]*\])", text)
    split = [r for r in results
             if re.search(rf"\[{B},({S_MAX},{heads}|{heads},{S_MAX}),{D}\]",
                          r)]
    assert not split, sorted(set(split))
    if kind == "int8":
        assert f"f32[{B},{S_MAX},{heads * D}]" not in results
    chunk, _pool = _paged_program(one_chip, heads, kind, "chunk")
    assert f"[1,{S_MAX},{heads},{D}]" in chunk


@pytest.mark.parametrize("role", ["decode", "chunk"])
@pytest.mark.parametrize("kind", ["fp32", "int8"])
@pytest.mark.parametrize("heads", [H, 16], ids=["large", "medium"])
def test_paged_program_compiles_for_other_pools(one_chip,
                                                no_compile_cache, heads,
                                                kind, role):
    """fp32 and int8 pools (an int8 tile is 32 rows, a page has 16)
    compile; what their text shows is in PERF.md §7."""
    text, pool = _paged_program(one_chip, heads, kind, role)
    assert pool in text


@pytest.mark.parametrize("n,k", [(1280, 1280), (5120, 1280),
                                 (1280, 5120), (50257, 1280)])
def test_dequant_matmul(one_chip, no_compile_cache, n, k):
    """Every projection shape of the block, and the vocabulary (not a
    multiple of the block: the last grid step overhangs)."""
    _compile(qz.dequant_matmul_pallas, one_chip, ((B, k), jnp.float32),
             ((n, k), jnp.int8), ((n,), jnp.float32))


def _named_kernels():
    """name -> (function, shapes): each kernel of the main path at its
    bf16 (dequant: int8) shapes, called under a scope as the model
    calls it."""
    qkv = ((2, H, S_MAX, D), jnp.bfloat16)
    q1 = ((B, H, 1, D), jnp.bfloat16)
    kv = ((B, H, S_MAX, D), jnp.bfloat16)
    return {
        "flash_attention_fwd": (
            lambda q, k, v: att.flash_attention_pallas(q, k, v,
                                                       causal=True),
            (qkv, qkv, qkv)),
        "decode_attention": (
            att.decode_attention_pallas,
            (q1, kv, kv, ((B,), jnp.int32))),
        "dequant_matmul": (
            qz.dequant_matmul_pallas,
            (((B, 1280), jnp.float32), ((5120, 1280), jnp.int8),
             ((5120,), jnp.float32))),
    }


@pytest.mark.parametrize("name", ["flash_attention_fwd",
                                  "decode_attention",
                                  "dequant_matmul"])
def test_kernel_is_named_in_the_compiled_program(one_chip,
                                                 no_compile_cache, name):
    """What the device trace shows of a kernel is its HLO instruction:
    the ``name=`` of the ``pallas_call`` is the instruction's name and
    stands in ``op_name`` under the caller's scope, and
    ``tpu_custom_call`` still lies inside the 1,200 characters of the
    instruction that the benchmark's trace reduction keeps
    (``chipbench/trace_reduce.TEXT_LIMIT``), where the roofline
    patterns look for it."""
    fn, shapes = _named_kernels()[name]

    def scoped(*args):
        with jax.named_scope("attn"):
            return fn(*args)

    text = _compile(scoped, one_chip, *shapes)
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    inst = calls[0].removeprefix("ROOT ")
    assert inst.split(" = ")[0].lstrip("%").split(".")[0] == name, \
        inst[:120]
    assert f"/attn/{name}/" in inst.split("op_name=")[1].split('"')[1]
    # the trace prints each operand with its shape and layout, which
    # this text gives once more under operand_layout_constraints
    shapes = inst.split("operand_layout_constraints={")[1].split("}, ")[0]
    assert inst.index("tpu_custom_call") + len(shapes) < 1200


# -- the serving expert layer's grouped product (ops/moe.py), at the
# -- published widths of dots3-note-prev: 32 held experts of 5120 x 1536
E_HELD, D_MODEL, F_EXPERT, TOP_K = 32, 5120, 1536, 8


@pytest.mark.parametrize("tokens,tm", [(32, 32), (512, 128)],
                         ids=["tick", "chunk"])
@pytest.mark.parametrize("kn", [(D_MODEL, F_EXPERT), (F_EXPERT, D_MODEL)],
                         ids=["gate-up", "down"])
def test_moe_grouped_matmul(one_chip, no_compile_cache, tokens, tm, kn):
    """A decode tick's rows (32 slots x top 8, tiles of 32) and a prefill
    chunk's (512 x 8, tiles of 128): the grid's middle axis is the
    number of tiles that hold rows, known only on the device, and the
    kernel keeps its name in the compiled program."""
    from mxnet_tpu.ops import moe
    k, n = kn
    m = moe.rows_for(tokens * TOP_K, E_HELD, tm)

    def fn(x, w, tile_expert, n_active):
        with jax.named_scope("moe"):
            return moe.grouped_matmul_pallas(x, w, tile_expert,
                                             n_active[0], tm)

    text = _compile(fn, one_chip, ((m, k), jnp.bfloat16),
                    ((E_HELD, k, n), jnp.bfloat16),
                    ((m // tm,), jnp.int32), ((1,), jnp.int32))
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    inst = calls[0].removeprefix("ROOT ")
    assert inst.split(" = ")[0].lstrip("%").split(".")[0] \
        == moe.KERNEL_NAME, inst[:120]
    assert f"/moe/{moe.KERNEL_NAME}" in \
        inst.split("op_name=")[1].split('"')[1]


# -- the phi4flash family at its published widths: the scan kernel of a
# -- 512-token chunk over 5120 channels x 16 states, and the pool's readers
# -- under grouped heads (40 query heads over a pool of 20 K heads x 64 and,
# -- read as pairs, 10 V heads x 128; page 64)
def test_ssm_chunk_scan(one_chip, no_compile_cache):
    from mxnet_tpu.ops import ssm
    t, ch, n = 512, 5120, 16
    f32 = jnp.float32

    def fn(x, dt, a, b, c, d, h0):
        with jax.named_scope("ssm_scan"):
            return ssm.selective_scan_pallas(x, dt, a, b, c, d, h0)

    text = _compile(fn, one_chip, ((t, ch), f32), ((t, ch), f32),
                    ((n, ch), f32), ((t, n), f32), ((t, n), f32),
                    ((ch,), f32), ((n, ch), f32))
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    inst = calls[0].removeprefix("ROOT ")
    assert inst.split(" = ")[0].lstrip("%").split(".")[0] \
        == ssm.KERNEL_NAME, inst[:120]
    assert f"/ssm_scan/{ssm.KERNEL_NAME}" in \
        inst.split("op_name=")[1].split('"')[1]


GQ, GKV, GPAGE, GS = 40, 20, 64, 5120


@pytest.mark.parametrize("kv_heads", [(GKV, GKV), (GKV, GKV // 2)],
                         ids=["grouped", "differential"])
def test_rows_decode_with_grouped_heads(one_chip, no_compile_cache,
                                        kv_heads):
    """The pool's readers of ``Phi4FlashModel``'s tick, ``gather_rows``
    and ``rows_decode_attention``: 40 query heads over 20 K/V heads, and
    the differential layers' reading of the same rows (V as 10 heads
    twice as wide). The gathered view is never re-tiled into heads."""
    slots, p_max = 8, GS // GPAGE
    pool = ((slots * p_max + 1, GPAGE, GKV * D), jnp.bfloat16)

    def fn(q, k, v, table, valid):
        return att.rows_decode_attention(
            q, att.gather_rows(k, table), att.gather_rows(v, table), valid,
            kv_heads)

    text = _compile(fn, one_chip, ((slots, GQ, D), jnp.bfloat16), pool,
                    pool, ((slots, p_max), jnp.int32),
                    ((slots, GS), jnp.bool_), kernel=False)
    wide = GKV * D // kv_heads[1]
    assert f"f32[{slots},{GQ},{wide}]" in text
    assert f"[{slots},{GS},{GKV},{D}]" not in text


def test_phi4flash_tick_has_the_operations_ssm_share_reads(
        one_chip, no_compile_cache):
    """``model.ssm_share`` finds the tick's state update by the SHAPE of
    its result (a layer's float32 states of all 32 slots), which is the
    compiler's to choose: the decode program of ``Phi4FlashModel`` at the
    published widths and the cell's 32 slots, compiled for the described
    v5e, holds operations whose short name the metric's pattern matches.
    Should a compiler fuse the update into an operation of another shape,
    this fails before the metric falls silent."""
    import json
    import os
    from chipbench import trace_reduce
    from mxnet_tpu.gluon.model_zoo.phi4flash import Phi4FlashModel
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "phi4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench", "layer_metrics",
                           "model.ssm_share.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    model, serve = cfg["model"], cfg["serve"]
    net = Phi4FlashModel(**{k: model[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "sliding_window", "mb_per_layer", "layer_norm_eps", "d_state",
        "d_conv", "expand", "dt_rank", "prefill_chunk")},
        max_length=serve["max_length"])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    slots, ps = serve["max_slots"], serve["page_size"]
    params = {("top" if g is None else g): {
        k: jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype),
                                sharding=one_chip) for k, p in ps_.items()}
        for g, ps_ in net._params.items()}
    cache = on_chip(jax.eval_shape(lambda: net.init_paged_cache(
        slots, slots * serve["max_length"] // ps + 1, ps,
        serve["max_length"])))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    text = jax.jit(net._decode_body).lower(
        params, rows, rows, cache).compile().as_text()
    names = [trace_reduce.short(line.strip().removeprefix("ROOT "))[0]
             for line in text.splitlines() if " = " in line]
    # a fusion is an operation the device runs and the trace times
    hits = [n for n in names
            if n.startswith("fusion ") and pattern.search(n)]
    assert hits, sorted({n for n in names if "5120]" in n})[:20]


# -- the xing4 family at its published widths: the decode program of the
# -- cell's 32 slots, whose hyper-connections model.mhc_share finds by shape
def test_xing4_tick_has_the_operations_mhc_share_reads(
        one_chip, no_compile_cache, monkeypatch):
    """``model.mhc_share`` finds the hyper-connections' operations by the
    SHAPE of their results (and the Sinkhorn rounds and the read mix by
    the fusion's name too), which are the compiler's to choose: the decode
    program of ``Xing4Model`` at the published widths and the cell's 32
    slots, compiled for the described v5e with the expert kernel as the
    chip runs it, holds operations that each alternative of the metric's
    pattern matches, and the expert kernel under the name
    ``model.moe_share`` reads. Should a compiler fuse them otherwise, this
    fails before the metric falls silent."""
    import json
    import os
    from chipbench import trace_reduce
    from chipbench.families.xing4 import program
    from mxnet_tpu.gluon.model_zoo.xing4 import Xing4Model
    from mxnet_tpu.ops import moe
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "xing4-29b-a4b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench", "layer_metrics",
                           "model.mhc_share.json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    model, serve = cfg["model"], cfg["serve"]
    net = Xing4Model(**{k: model[k] for k in program._KEYS},
                     max_length=serve["max_length"])
    # the program asks which backend runs it and would take the CPU's
    # ``jnp`` product: the test steers it to the chip's kernel
    monkeypatch.setattr(att, "_use_pallas", lambda: True)

    def on_chip(p):
        return jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype),
                                    sharding=one_chip)

    layers = [{k: on_chip(p) for k, p in net._params[li].items()}
              for li in range(model["num_hidden_layers"])]
    top = {k: on_chip(p) for k, p in net._params[None].items()}
    slots, ps = serve["max_slots"], serve["page_size"]
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: net.init_paged_cache(
            slots, slots * serve["max_length"] // ps + 1, ps,
            serve["max_length"])))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    text = jax.jit(net._decode_body).lower(
        layers, top, rows, rows, cache).compile().as_text()
    names = [trace_reduce.short(line.strip().removeprefix("ROOT "))[0]
             for line in text.splitlines() if " = " in line]
    # a fusion is an operation the device runs and the trace times
    ran = [n for n in names if n.startswith(("fusion ", "custom-call "))]
    for part in pattern.split("|^"):
        rx = re.compile(part if part.startswith("^") else "^" + part)
        hits = [n for n in ran if rx.search(n)]
        # twelve sublayers: at least one such operation in each
        assert len(hits) >= 12, (part, sorted(set(ran))[:40])
    assert sum(n.startswith(f"custom-call {moe.KERNEL_NAME} ")
               for n in ran) == 3 * 4
    # no pool is copied and no view is split into heads
    assert not [n for n in ran if n.startswith("copy ") and "4609" in n]
