"""Compile-only, for a v5e that is described and not attached: the
decode step and the verify step at the served widths (d 2048, 16 heads
of 128, a bfloat16 pool of 2560 pages and the trash page, donated)
must update the pool IN PLACE. The optimized HLO may hold no `copy`
whose result has the pool's shape, and `input_output_alias` must name
every leaf of the pool.

What this guards (PERF.md section 6, PR 27): written as
`arr.at[dest, :, offset, :].set(rows)` the step's K/V write is a
scatter whose operand the TPU compiler lays out as {3,1,2,0}, against
the {3,2,1,0} of the donated pool and of `paged_decode_attention`: two
layout changes of a whole 168 MB pool per layer for K and for V each,
65% of the device time of a served decode step. About 8 s a compile, no
chip time. Since PR 31 the kernel at these shapes sweeps blocks of 8
pages that it copies out of the pool itself (the pool is an operand
left in HBM): each call must still be handed the pool as it lies,
row-major, whatever the block. And the decode steps may hold no copy of
a `Wq`, `Wk` or `Wv` weight (the compiler had met the attention's
heads-major layout by re-laying out the weights).

The topology is described inside a module fixture, never at import, and
the tests skip where it cannot be described (the same set-up as
tests/benchmark_suite/test_compile_v5e.py). All of them live in this
one file: the worker that is given it loads libtpu and keeps it."""

import math
import os
import re

import pytest

pytestmark = pytest.mark.pallas

SLOTS, PAGES, PAGE, HEADS, HD = 16, 2560, 16, 16, 128
POOL_SHAPE = (PAGES + 1, HEADS, PAGE, HD)
N_LAYERS = 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to JAX's persistent
    cache but cannot be read back without one; keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _cfg():
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    return TransformerConfig(vocab_size=50257, d_model=HEADS * HD,
                             n_heads=HEADS, n_layers=N_LAYERS, d_ff=8192,
                             max_len=2048, dtype=jnp.bfloat16)


def _described(one_chip, cfg):
    """(params, pool, table) as shapes on the described chip, and
    `vec(*shape)` for an int32 argument of that shape."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import \
        init_transformer_params
    from deeplearning4j_tpu.serving.paged_kv import (init_paged_pool,
                                                     pages_per_slot)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_transformer_params(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(
        lambda: init_paged_pool(cfg, PAGES, PAGE)))
    assert pool.layers[0]["k"].shape == POOL_SHAPE

    def vec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    return params, pool, vec(SLOTS, pages_per_slot(cfg, PAGE)), vec


def _assert_pool_updated_in_place(text):
    dims = ",".join(str(n) for n in POOL_SHAPE)
    copies = re.findall(
        rf"^.*= bf16\[{dims}\]\{{[^}}]*\}} copy\(.*$", text, re.M)
    assert not copies, (
        f"{len(copies)} pool-shaped copies in the step's program, "
        f"first: {copies[0][:200]}")
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text, re.S)
    assert alias, "the compiled module aliases no input to an output"
    # one alias per pool leaf: K and V of every layer
    leaves = re.findall(r"(?:may|must)-alias", alias.group(1))
    assert len(leaves) == 2 * N_LAYERS, alias.group(1)


def _assert_kernel_reads_the_pool_as_it_lies(text, shapes, calls):
    """Every `paged_decode_attention` call constrains its K and V
    operands to the pool's shape, row-major: the layout of the donated
    pool and of the step's write."""
    found = re.findall(
        r"^.*paged_decode_attention.*custom-call\(.*"
        r"operand_layout_constraints=\{(.*?\})\}, ", text, re.M)
    assert len(found) == calls, len(found)
    wanted = {"bf16[" + ",".join(str(n) for n in shape) + "]{3,2,1,0}"
              for shape in shapes}
    for constraints in found:
        pools = re.findall(r"bf16\[\d+,\d+,\d+,\d+\]\{[\d,]*\}",
                           constraints)[-2:]
        assert len(set(pools)) == 1 and pools[0] in wanted, constraints


@pytest.fixture(scope="module")
def gpt_step_text(one_chip, no_compile_cache):
    """The step as `DecodeLoop` jits it: `paged_kinds.decode_step` on
    the paged lane with the argmax fed back, under a `lax.scan` of length
    1 (horizon 1), pool donated; its optimized HLO."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving import paged_kinds

    cfg = _cfg()
    params, pool, table, vec = _described(one_chip, cfg)

    def step_fn(params, tokens, pool, table, lengths, stop):
        def inner(carry, _):
            tokens, lengths, pool = carry
            act = lengths < stop
            logits, pool, _ = paged_kinds.decode_step(
                params, tokens, pool, {"full": table}, lengths, act, cfg,
                kernel="pallas")
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tokens = jnp.where(act, nxt, tokens)
            lengths = lengths + act.astype(lengths.dtype)
            return (tokens, lengths, pool), nxt

        (tokens, lengths, pool), toks = jax.lax.scan(
            inner, (tokens, lengths, pool), None, length=1)
        return toks, tokens, lengths, pool

    return jax.jit(step_fn, donate_argnums=(2,)).lower(
        params, vec(SLOTS), pool, table, vec(SLOTS),
        vec(SLOTS)).compile().as_text()


def test_decode_step_holds_no_pool_shaped_copy(gpt_step_text):
    _assert_kernel_reads_the_pool_as_it_lies(gpt_step_text, [POOL_SHAPE],
                                             N_LAYERS)
    _assert_pool_updated_in_place(gpt_step_text)


def test_verify_step_holds_no_pool_shaped_copy(one_chip,
                                               no_compile_cache):
    """`paged_kinds.verify_step` at W 4 on the paged lane, pool donated,
    as `DecodeLoop`'s `verify_fn` jits it."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving import paged_kinds

    cfg = _cfg()
    params, pool, table, vec = _described(one_chip, cfg)

    def verify_fn(params, tokens, pool, table, lengths, widths):
        logits, pool, _ = paged_kinds.verify_step(
            params, tokens, pool, {"full": table}, lengths, widths, cfg,
            kernel="pallas")
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

    text = jax.jit(verify_fn, donate_argnums=(2,)).lower(
        params, vec(SLOTS, 4), pool, table, vec(SLOTS),
        vec(SLOTS)).compile().as_text()
    # one single-query pass a draft column a layer
    _assert_kernel_reads_the_pool_as_it_lies(text, [POOL_SHAPE],
                                             4 * N_LAYERS)
    _assert_pool_updated_in_place(text)


TWO_KIND_PAGES = {"full": 2048, "window": 1056}


@pytest.fixture(scope="module")
def two_kind_step_text(one_chip, no_compile_cache):
    """The decode step of the block with grouped K/V heads, window and
    full layers and a held-expert layer (`paged_kinds.decode_step`, as
    `DecodeLoop` jits it for a model with kinds of layer) at the served
    widths: 128 query heads over 8 K/V heads of 128, bfloat16 pools of
    2048 full and 1056 window pages of 128 tokens, donated. One window
    and one full layer: what the compiler does to a pool it does to
    each; the same `lax.scan` of length 1 as every model's step. The
    grouped expert products are the megablox kernel, as on the chip (the
    backend here is the CPU, so the test says "tpu"). Its optimized
    HLO."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import moe_transformer as moe
    from deeplearning4j_tpu.serving import paged_kinds

    cfg = moe.MoEConfig(
        vocab_size=32768, d_model=4096, n_heads=128, n_kv_heads=8,
        head_dim=128, d_ff=4096, layer_kinds=("window", "full"),
        window=4096, n_experts=128, experts_per_token=8, n_shared=4,
        n_held=16, rope_theta=50000.0, max_len=8192,
        dtype=jnp.bfloat16).check()
    pages = TWO_KIND_PAGES

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def vec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: moe.init_moe_params(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(
        lambda: paged_kinds.init_pool(cfg, pages, 128)))
    tables = {kind: vec(32, 64) for kind in pages}

    def step_fn(params, tokens, pool, table, lengths, stop):
        def inner(carry, _):
            tokens, lengths, pool = carry
            act = lengths < stop
            logits, pool, pairs = paged_kinds.decode_step(
                params, tokens, pool, table, lengths, act, cfg,
                kernel="pallas")
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (jnp.where(act, nxt, tokens),
                    lengths + act.astype(lengths.dtype), pool), (nxt, pairs)

        (tokens, lengths, pool), out = jax.lax.scan(
            inner, (tokens, lengths, pool), None, length=1)
        return out, tokens, lengths, pool

    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        return jax.jit(step_fn, donate_argnums=(2,)).lower(
            params, vec(32), pool, tables, vec(32),
            vec(32)).compile().as_text()
    finally:
        jax.default_backend = backend


def test_two_kind_decode_step_holds_no_pool_shaped_copy(two_kind_step_text):
    text = two_kind_step_text
    assert "%gmm" in text
    _assert_kernel_reads_the_pool_as_it_lies(
        text, [(n + 1, 8, 128, 128) for n in TWO_KIND_PAGES.values()], 2)
    for n in TWO_KIND_PAGES.values():
        copies = re.findall(
            rf"^.*= bf16\[{n + 1},8,128,128\]\{{[^}}]*\}} copy\(.*$",
            text, re.M)
        assert not copies, copies[0][:200]
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text, re.S)
    assert alias, "the compiled module aliases no input to an output"
    assert len(re.findall(r"(?:may|must)-alias", alias.group(1))) == 4


# ---------------------------------- the projections' weights as they lie
#: what hands a value on unchanged, or moves it between memories: a
#: weight reached through these is the weight itself
_CARRIERS = ("bitcast", "copy-start", "copy-done", "slice-start",
             "slice-done", "get-tuple-element")


def _instructions(text):
    """{name: (opcode, operand names, line)} of every instruction."""
    found = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        op = m and re.search(r"\s([a-z][\w\-]*)\(", line[m.end():])
        if op:
            args = line[m.end() + op.end():]
            found[m.group(1)] = (op.group(1), re.findall(
                r"%([\w.\-]+)", args[:args.find(")")]), line)
    return found


def _projection_weight_copies(text):
    """The `copy` instructions whose operand is a parameter `Wq`, `Wk`
    or `Wv`, reached through bitcasts, the prefetch's async copies and
    slices, and the custom call that joins prefetched slices."""
    found = _instructions(text)

    def weights(name, depth=0):
        if name not in found or depth > 16:
            return set()
        op, args, line = found[name]
        if op == "parameter":
            return {name} if re.match(r"\s*%params__\S*___W[qkv]__",
                                      line) else set()
        if op in _CARRIERS or (op == "custom-call"
                               and "ConcatBitcast" in line):
            return set().union(*(weights(a, depth + 1) for a in args))
        return set()

    return [(name, sorted(weights(args[0])))
            for name, (op, args, _) in found.items()
            if op == "copy" and args and weights(args[0])]


@pytest.mark.parametrize("step", ["gpt_step_text", "two_kind_step_text"])
def test_decode_step_copies_no_projection_weight(step, request):
    """cgpt-1.3b's step (16 heads of 128 at d 2048) and command-a-plus
    ep8's (128 query heads over 8 K/V heads at d 4096): given the
    products of `Wq`, `Wk` and `Wv` free to take the heads-major layout
    the attention reads, the compiler re-laid out each WEIGHT, a whole
    matrix copied a layer a step (72 `bf16[2048,2048]` copies a step of
    24 layers, 4 of `bf16[16384,4096]` a step of ep8's four; PERF.md
    section 6). A step leaves its products row-major and
    splits the rows: no copy of a projection's weight."""
    text = request.getfixturevalue(step)
    assert re.search(r"%params__\S*___Wq__\S* = \S+ parameter\(", text), \
        "the step takes no Wq: the walk would find nothing to refuse"
    copies = _projection_weight_copies(text)
    assert not copies, f"{len(copies)} weight copies, first {copies[0]}"


# ------------------------------------- a kind that is not pages (PR 35)
HYBRID_SLOTS, HYBRID_PAGES = 64, 4096


def _hybrid(one_chip):
    """One period `linear, linear, linear, full` of the block of
    `models/hybrid_transformer.py` at the served widths (d 2048; linear
    layers of 16 key and 32 value heads of 128, a state of (32, 128, 128)
    float32 a slot; 16 query heads over 2 K/V heads of 256; 64 of 512
    experts held), 64 slots, a bfloat16 pool of 4096 pages of 128
    tokens: (cfg, params, pool, vec) as shapes on the described chip."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import hybrid_transformer as hybrid
    from deeplearning4j_tpu.serving import paged_kinds

    cfg = hybrid.HybridConfig(
        vocab_size=18992, d_model=2048, n_heads=16, n_kv_heads=2,
        head_dim=256, d_ff=512,
        layer_kinds=("linear", "linear", "linear", "full"),
        n_experts=512, experts_per_token=10, n_shared=1, n_held=64,
        max_len=8192, dtype=jnp.bfloat16).check()

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def vec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: hybrid.init_hybrid_params(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(
        lambda: paged_kinds.init_pool(cfg, {"full": HYBRID_PAGES}, 128,
                                      slots=HYBRID_SLOTS)))
    assert pool.layers[0]["state"].shape == (HYBRID_SLOTS, 32, 128, 128)
    assert pool.layers[0]["conv"].shape == (HYBRID_SLOTS, 3 * 8192)
    assert pool.page_size == 128 and pool.n_pages == HYBRID_PAGES
    return cfg, params, pool, vec


def _compiled_as_on_the_chip(fn, donate, *args):
    import jax

    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        return jax.jit(fn, donate_argnums=donate).lower(
            *args).compile().as_text()
    finally:
        jax.default_backend = backend


def _assert_state_and_pool_updated_in_place(text):
    """No copy of a whole cache array of either kind, and one alias a
    leaf: state and kept columns of three linear layers, K and V of the
    full one."""
    for shape in (rf"f32\[{HYBRID_SLOTS},32,128,128\]",
                  rf"bf16\[{HYBRID_SLOTS},24576\]",
                  rf"bf16\[{HYBRID_PAGES + 1},2,128,256\]"):
        copies = re.findall(rf"^.*= {shape}\{{[^}}]*\}} copy\(.*$", text,
                            re.M)
        assert not copies, copies[0][:200]
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text, re.S)
    assert alias, "the compiled module aliases no input to an output"
    assert len(re.findall(r"(?:may|must)-alias", alias.group(1))) == 8


def test_hybrid_decode_step_updates_state_in_place(one_chip,
                                                   no_compile_cache):
    """The decode step of a model with linear layers, as `DecodeLoop`
    jits it: each slot's recurrent state goes through `gdn_update` once
    a layer and comes back in the donated buffer, the kept columns and
    the full layer's pool likewise; the paged kernel takes heads of
    256."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving import paged_kinds

    cfg, params, pool, vec = _hybrid(one_chip)
    s = HYBRID_SLOTS

    def step_fn(params, tokens, pool, table, lengths, stop):
        def inner(carry, _):
            tokens, lengths, pool = carry
            act = lengths < stop
            logits, pool, pairs = paged_kinds.decode_step(
                params, tokens, pool, table, lengths, act, cfg,
                kernel="pallas")
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (jnp.where(act, nxt, tokens),
                    lengths + act.astype(lengths.dtype), pool), (nxt, pairs)

        (tokens, lengths, pool), out = jax.lax.scan(
            inner, (tokens, lengths, pool), None, length=1)
        return out, tokens, lengths, pool

    text = _compiled_as_on_the_chip(
        step_fn, (2,), params, vec(s), pool, {"full": vec(s, 64)}, vec(s),
        vec(s))
    assert "%gmm" in text
    assert len(re.findall(r"^.*gdn_update.*custom-call\(", text,
                          re.M)) == 3
    assert len(re.findall(r"^.*paged_decode_attention.*custom-call\(",
                          text, re.M)) == 1
    _assert_state_and_pool_updated_in_place(text)


def test_hybrid_prefill_writes_the_slot_s_state_in_place(one_chip,
                                                         no_compile_cache):
    """One row of the 8,192 bucket, as `DecodeLoop`'s `prefill_fn` jits
    it: the chunked scan a linear layer (one kernel, `gdn_scan`, that
    since PR 38 does a chunk's state-free half and its state half in
    one grid step), grouped-head flash at heads of 256 in the full one,
    the row's final state scattered to its slot of the donated arrays."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving import paged_kinds

    cfg, params, pool, vec = _hybrid(one_chip)

    def prefill_fn(params, tokens, true_len, pool, page_ids):
        logits, pool, aux = paged_kinds.prefill(
            params, tokens, true_len, pool, page_ids, cfg)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32), aux), pool

    text = _compiled_as_on_the_chip(
        prefill_fn, (3,), params, vec(1, 8192), vec(1), pool,
        {"full": vec(1, 64), "linear": vec(1)})
    assert len(re.findall(r"^.*gdn_scan.*custom-call\(", text, re.M)) == 3
    assert len(re.findall(r"^.*flash_fwd.*custom-call\(", text,
                          re.M)) == 1
    _assert_state_and_pool_updated_in_place(text)


# ------------------------- how the expert layer's rows travel (PR 36)
def _expert_layer_text(one_chip, cfg, t):
    """Optimized HLO of `expert_layer` alone over `t` normed rows at the
    configuration's widths, bfloat16, as a prefill or a step holds it."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import moe_transformer as moe

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    d, f = cfg.d_model, cfg.d_ff
    experts = {"gate": (d, f), "up": (d, f), "down": (f, d)}
    p = {"router": on_chip((d, cfg.n_experts)),
         "experts": {k: on_chip((cfg.n_held,) + v)
                     for k, v in experts.items()},
         "shared": {k: on_chip((cfg.n_shared,) + v)
                    for k, v in experts.items()},
         "shared_gate": on_chip((d, cfg.n_shared))}
    return _compiled_as_on_the_chip(
        lambda p, h, valid: moe.expert_layer(p, h, cfg, valid), (),
        p, on_chip((t, d)), on_chip((t,), jnp.bool_))


def _calls(text: str, kernel: str):
    return re.findall(rf"^\s*(?:ROOT )?%{kernel}[.\d]* = .*custom-call\(",
                      text, re.M)


def _elements(shape: str) -> int:
    return math.prod(int(dim) for dim in shape.split(","))


def _expert_cfg(widths: str):
    """The expert layer's widths of the two served configurations."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import moe_transformer as moe

    if widths == "ep8":
        return moe.MoEConfig(
            vocab_size=8, d_model=4096, n_heads=128, n_kv_heads=8,
            head_dim=128, d_ff=4096, layer_kinds=("full",), window=4096,
            n_experts=128, experts_per_token=8, n_shared=4, n_held=16,
            dtype=jnp.bfloat16).check()
    return moe.MoEConfig(
        vocab_size=8, d_model=2048, n_heads=16, n_kv_heads=2,
        head_dim=256, d_ff=512, layer_kinds=("full",), window=0,
        n_experts=512, experts_per_token=10, n_shared=1, n_held=64,
        dtype=jnp.bfloat16, router_score="softmax",
        shared_combine="sigmoid_gate").check()


@pytest.mark.parametrize("widths", ["ep8", "q3n"])
def test_prefill_expert_layer_moves_rows_by_row_copies(one_chip,
                                                       no_compile_cache,
                                                       widths):
    """The expert layer of the 8,192-row prefill at the two served
    configurations' widths. What the parent's program held a layer
    (PERF.md section 6, PR 36): a row gather of a chunk and a copy of
    it before `gmm` read it, a `(chunk, d)` float32 product under the
    weights, and for the scatter-add a sort of the token indices, a
    second row gather, and a scatter into `(t, d)` float32. Now: the
    two kernels, the three grouped products between them, no scatter
    into `(t, d)`, no sort under `moe_experts`, no copy of the sorted
    rows `gmm` reads, and of the chunk's float32 result the one pass
    that lays it out as slabs."""
    from deeplearning4j_tpu.models import moe_transformer as moe

    cfg = _expert_cfg(widths)
    t, d = 8192, cfg.d_model
    most, chunk = moe._pair_chunk(t, cfg)
    assert chunk < most and chunk % 256 == 0
    text = _expert_layer_text(one_chip, cfg, t)
    # one body for every chunk: each kernel once, the three grouped
    # products once (a program's load from the compile cache grows with
    # the kernels it holds)
    for kernel, calls in (("moe_rows_in", 1), ("moe_rows_out", 1),
                          ("gmm", 3)):
        assert len(_calls(text, kernel)) == calls, kernel
    scatters = re.findall(r"^.*= (\w+)\[([\d,]+)\]\S* scatter\(.*$", text,
                          re.M)
    assert all(_elements(shape) < t for _, shape in scatters), scatters
    assert not re.findall(r"^.* sort\(.*moe_experts.*$", text, re.M)
    copies = [(dtype, shape) for dtype, shape in re.findall(
        r"^.*= (\w+)\[([\d,]+)\]\S* copy\(", text, re.M)
        if _elements(shape) == chunk * d]
    assert [dtype for dtype, _ in copies] == ["f32"], copies
    # and no gather builds the sorted rows (ep8's activation has their
    # shape: an expert is as wide as the model there)
    assert not re.findall(rf"^.*= bf16\[{chunk},{d}\]\S* "
                          r"(?:gather|copy)\(", text, re.M)
    assert not re.findall(r"^.*= \w+\[[\d,]+\]\S* gather\(.*moe_experts"
                          r"(?!.*jit\(gmm\)).*$", text, re.M)


def test_decode_step_expert_layer_takes_the_kernels_too(one_chip,
                                                        no_compile_cache):
    """The decode step's expert layer (64 rows at q3n's widths: a chunk
    of 256 of at most 768 pairs) is the same movement: one fork, on
    whether a chunk holds every pair, and no second form."""
    from deeplearning4j_tpu.models import moe_transformer as moe

    cfg = _expert_cfg("q3n")
    assert moe._pair_chunk(64, cfg) == (768, 256)
    text = _expert_layer_text(one_chip, cfg, 64)
    assert len(_calls(text, "moe_rows_in")) == 1
    assert len(_calls(text, "moe_rows_out")) == 1
    assert not re.findall(r"^.* sort\(.*moe_experts.*$", text, re.M)


# ------------------------ heads narrower than a lane tile (lfm2_moe's)
NARROW_SLOTS, NARROW_PAGES = 64, 2560


def test_narrow_head_decode_step_holds_no_pool_shaped_copy(
        one_chip, no_compile_cache):
    """lfm2's step: 32 query heads over 8 K/V heads of 64 in pages of
    128 (a conv layer beside, dense, to keep the compile short). The
    TPU lays such a pool out with its positions minor, so a kernel that
    took it row-major copied the K and V pools of every attention layer
    at every step (1.34 GB a step at the cell's 2,560 pages); the kernel
    reads it as it lies, and the step holds no copy of either form of
    the pool's shape and updates it in place."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import hybrid_transformer as hybrid
    from deeplearning4j_tpu.serving import paged_kinds

    cfg = hybrid.HybridConfig(
        vocab_size=1024, d_model=2048, n_heads=32, n_kv_heads=8,
        head_dim=64, d_ff=512, layer_kinds=("conv", "full"), n_experts=0,
        experts_per_token=0, n_shared=0, n_held=0, conv_kernel=3,
        max_len=8192, rms_eps=1e-5, attn_gate=False, tied_head=True,
        dtype=jnp.bfloat16).check()

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def vec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: hybrid.init_hybrid_params(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(
        lambda: paged_kinds.init_pool(cfg, {"full": NARROW_PAGES}, 128,
                                      slots=NARROW_SLOTS)))
    pool_shape = (NARROW_PAGES + 1, 8, 128, 64)
    assert pool.layers[1]["k"].shape == pool_shape
    s = NARROW_SLOTS

    def step_fn(params, tokens, pool, table, lengths, stop):
        act = lengths < stop
        logits, pool, _ = paged_kinds.decode_step(
            params, tokens, pool, table, lengths, act, cfg,
            kernel="pallas")
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

    text = _compiled_as_on_the_chip(
        step_fn, (2,), params, vec(s), pool, {"full": vec(s, 64)}, vec(s),
        vec(s))
    assert len(re.findall(r"^.*paged_decode_attention.*custom-call\(",
                          text, re.M)) == 1
    n, h, ps, hd = pool_shape
    for dims in ((n, h, ps, hd), (n, h, hd, ps)):
        shape = ",".join(str(x) for x in dims)
        copies = re.findall(rf"^.*= bf16\[{shape}\]\{{[^}}]*\}} "
                            r"(?:copy|transpose)\(.*$", text, re.M)
        assert not copies, f"{len(copies)} pool copies: {copies[0][:200]}"
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text, re.S)
    assert alias, "the compiled module aliases no input to an output"
    # K and V of the full layer, the conv layer's kept columns
    assert len(re.findall(r"(?:may|must)-alias", alias.group(1))) == 3
