"""Compile-only guards for the chip path, run on the CPU.

The TPU compiler is installed with jax and compiles for a chip that is
described and not attached (`jax.experimental.topologies`). Nothing executes
here: these tests say "the v5e compiler accepts this kernel / this decode
step at real widths", which interpret mode cannot (tiling and VMEM limits
only exist in the real lowering). They are skipped where the topology cannot
be described. The file sorts early on purpose, ahead of the slow
multi-device suites.

Also here: unit tests for the places where a device decision used to fall
back silently (unknown accelerator peaks, a failing kernel probe under
``attn_impl="auto"``, where the compile cache goes).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.models import llama
from dynamo_tpu.ops import attention as A
from dynamo_tpu.utils import jaxenv, roofline

os.environ.setdefault("TPU_LOG_DIR", "disabled")

PAGE = 64
# (Hq, Hkv, Dh): llama-3.2-1b, a Dh=128 GQA model, a Dh=256 (Gemma-class)
GEOMETRIES = [(32, 8, 64), (32, 8, 128), (16, 8, 256)]
VARIANTS = {
    "plain": {},
    "window": {"window": 1024},
    "softcap": {"softcap": 50.0, "scale": 0.0625},
}


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device, with the persistent compile cache off: a
    compile for a described device is written to the cache but cannot be
    read back without a chip, so a warm cache would only add warnings."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this image
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(device, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(device))


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_text(v5e, geom, B, T, S, **kw) -> str:
    Hq, Hkv, Dh = geom
    return _compiled_text(
        lambda q, k, v, qp, kp, kv: A.flash_attention(
            q, k, v, qp, kp, kv, interpret=False, **kw),
        _sds(v5e, (B, T, Hq, Dh), jnp.bfloat16),
        _sds(v5e, (B, S, Hkv, Dh), jnp.bfloat16),
        _sds(v5e, (B, S, Hkv, Dh), jnp.bfloat16),
        _sds(v5e, (B, T), jnp.int32), _sds(v5e, (B, S), jnp.int32),
        _sds(v5e, (B, S), jnp.bool_))


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("geom", GEOMETRIES, ids=lambda g: "x".join(map(str, g)))
def test_flash_compiles_for_v5e(v5e, geom, variant):
    assert "tpu_custom_call" in _flash_text(v5e, geom, 8, 128, 1024,
                                            **VARIANTS[variant])


@pytest.mark.parametrize("T,S", [(5, 2176), (32, 80), (512, 2176)])
def test_flash_compiles_at_engine_edge_shapes(v5e, T, S):
    """Shapes the engine really produces besides power-of-two buckets: a
    spec-verify chunk of k+1 tokens, a sub-128 context, and the last context
    bucket (max_context + pad, rounded to 128)."""
    assert "tpu_custom_call" in _flash_text(v5e, GEOMETRIES[0], 4, T, S)


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("geom", GEOMETRIES, ids=lambda g: "x".join(map(str, g)))
def test_paged_compiles_for_v5e(v5e, geom, variant):
    Hq, Hkv, Dh = geom
    B, P = 32, 34                       # 34 pages: max_context 2048 + pad
    n_pages = B * P + 1
    txt = _compiled_text(
        lambda q, k, v, pt, ln: A.paged_attention(
            q, k, v, pt, ln, interpret=False, **VARIANTS[variant]),
        _sds(v5e, (B, Hq, Dh), jnp.bfloat16),
        _sds(v5e, (Hkv, n_pages, PAGE, Dh), jnp.bfloat16),
        _sds(v5e, (Hkv, n_pages, PAGE, Dh), jnp.bfloat16),
        _sds(v5e, (B, P), jnp.int32), _sds(v5e, (B,), jnp.int32))
    assert "tpu_custom_call" in txt


# the cells' decode kernels as their programs call them (PERF.md section 4):
# lanes, q heads, kv heads, K row as stored, V row, tokens a pool row, table
# pages, window, sink, keep mask
CELL_KERNELS = {
    "qwen2": (32, 12, 2, 128, 128, 1, 18, None, False, False),
    "mistral": (16, 32, 8, 128, 128, 1, 34, None, False, False),
    "mimo-full": (32, 64, 4, 256, 128, 1, 128, None, False, False),
    "mimo-window": (32, 64, 8, 256, 128, 1, 128, 128, True, False),
    "granite": (64, 32, 8, 64, 64, 2, 32, None, False, False),
    "keye": (12, 32, 4, 128, 128, 1, 232, None, False, True),
}


@pytest.mark.parametrize("cell", list(CELL_KERNELS))
def test_the_kernel_that_copies_live_pages_compiles_at_the_cells_shapes(
        v5e, cell):
    """The dma kernel with its loop over a block's LIVE pages (dynamic
    bounds, a dynamic page of the buffer as a copy's target) and the V buffer
    zeroed at the first grid step, at every cell's shapes and variant, the
    new rows written by the kernel: the v5e compiler takes it, and the call
    keeps its first result type, by which the chip's trace names it."""
    B, Hq, Hkv, Dk, Dv, fold, P, window, sunk, selected = CELL_KERNELS[cell]
    L, n_pages, bf, i32 = 2, 40, jnp.bfloat16, jnp.int32
    extra, extra_args = [], []
    if sunk:
        extra.append("sink")
        extra_args.append(_sds(v5e, (Hq,), jnp.float32))
    if selected:
        extra.append("keep")
        extra_args.append(_sds(v5e, (B, P * PAGE), jnp.bool_))

    def call(q, k, v, pt, ln, ly, kn, vn, *rest):
        return A.paged_attention(
            q, k, v, pt, ln, ly, interpret=False, window=window,
            new=(kn, vn), **dict(zip(extra, rest)),
            **({"fold": fold} if fold > 1 else {}))

    txt = _compiled_text(
        call, _sds(v5e, (B, Hq, Dk), bf),
        _sds(v5e, (L, Hkv, n_pages, PAGE // fold, fold * Dk), bf),
        _sds(v5e, (L, Hkv, n_pages, PAGE // fold, fold * Dv), bf),
        _sds(v5e, (B, P), i32), _sds(v5e, (B,), i32), _sds(v5e, (), i32),
        _sds(v5e, (B, Hkv, Dk), bf), _sds(v5e, (B, Hkv, Dv), bf),
        *extra_args)
    assert txt.count('custom_call_target="tpu_custom_call"') == 1
    assert f"(bf16[{B},{Hkv},{Hq // Hkv},{Dv}]" in txt


@pytest.mark.parametrize("blocks_mib, heads", [(8, 64), (2, 16)])
def test_the_state_kernel_compiles_at_the_cells_shapes(v5e, monkeypatch,
                                                       blocks_mib, heads):
    """``ops.state.state_step`` over granite's whole state pool (36 layers x
    64 lanes x 64 heads x 64 x 128 float32) with a traced layer and a traced
    served-lane list: the v5e compiler takes it with a whole lane a block
    (what the cell runs) and with the lane cut by heads (a larger state),
    the pool is aliased through the call, and the program holds no copy of
    the pool or of a layer of it."""
    from dynamo_tpu.ops import state as S

    monkeypatch.setattr(S, "_STATE_BLOCKS_BYTES", blocks_mib << 20)
    L, B, H, P, N = 36, 64, 64, 64, 128
    assert S.head_block(H, P, N) == heads
    f32 = jnp.float32

    def step(pool, layer, active, a, dtx, Bm, Cm):
        return S.state_step(pool, layer, *S.served_lanes(active), a, dtx, Bm,
                            Cm)

    compiled = jax.jit(step, donate_argnums=0).lower(
        _sds(v5e, (L, B, H, P, N), f32), _sds(v5e, (), jnp.int32),
        _sds(v5e, (B,), jnp.bool_), _sds(v5e, (B, H), f32),
        _sds(v5e, (B, H, P), f32), _sds(v5e, (B, N), f32),
        _sds(v5e, (B, N), f32)).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= L * B * H * P * N * 4
    assert mem.temp_size_in_bytes < 8 << 20
    assert not [ln for ln in txt.splitlines() if " copy(" in ln
                and f"{B},{H},{P},{N}]" in ln]


def test_a_batch_past_vmem_compiles_in_groups_of_lanes(v5e):
    """256 lanes at llama-8b's heads: the per-lane operands (queries, new
    rows, output: 128 KiB a lane as VMEM lays them out) do not fit whole, so
    the call walks them in grid steps of a group each, blocks through the
    grid's pipeline; the v5e compiler takes that form too."""
    B, Hq, Hkv, D, P, L, n_pages = 256, 32, 8, 128, 32, 2, 40
    bf, i32 = jnp.bfloat16, jnp.int32
    a_lane = (2 * A._vmem_bytes((Hkv, Hq // Hkv, D), bf)
              + 2 * A._vmem_bytes((1, Hkv * D), bf))
    assert A._lane_groups(B, a_lane) == 8
    txt = _compiled_text(
        lambda q, k, v, pt, ln, ly, kn, vn: A.paged_attention(
            q, k, v, pt, ln, ly, interpret=False, new=(kn, vn)),
        _sds(v5e, (B, Hq, D), bf), _sds(v5e, (L, Hkv, n_pages, PAGE, D), bf),
        _sds(v5e, (L, Hkv, n_pages, PAGE, D), bf), _sds(v5e, (B, P), i32),
        _sds(v5e, (B,), i32), _sds(v5e, (), i32),
        _sds(v5e, (B, Hkv, D), bf), _sds(v5e, (B, Hkv, D), bf))
    assert txt.count('custom_call_target="tpu_custom_call"') == 1
    assert f"(bf16[{B},{Hkv},{Hq // Hkv},{D}]" in txt


def test_the_latent_decode_kernel_compiles_at_the_cells_shapes(v5e):
    """deepseek-v2-5l's decode kernel as its program calls it: 16 lanes, all
    128 heads against ONE row a key (the shared rotary key's pool, 128 lanes
    as stored, and the compressed vectors' pool, 512 wide), the queries'
    second part as one more operand, the two new rows written by the
    kernel, 258 pages a lane."""
    B, Hq, Dk, Dv, P, L, n_pages = 16, 128, 128, 512, 258, 5, 40
    bf, i32 = jnp.bfloat16, jnp.int32
    txt = _compiled_text(
        lambda q, ql, k, v, pt, ln, ly, kn, vn: A.paged_attention(
            q, k, v, pt, ln, ly, interpret=False, scale=0.1147, latent=ql,
            new=(kn, vn)),
        _sds(v5e, (B, Hq, Dk), bf), _sds(v5e, (B, Hq, Dv), bf),
        _sds(v5e, (L, 1, n_pages, PAGE, Dk), bf),
        _sds(v5e, (L, 1, n_pages, PAGE, Dv), bf),
        _sds(v5e, (B, P), i32), _sds(v5e, (B,), i32), _sds(v5e, (), i32),
        _sds(v5e, (B, 1, Dk), bf), _sds(v5e, (B, 1, Dv), bf))
    assert txt.count('custom_call_target="tpu_custom_call"') == 1
    assert f"(bf16[{B},1,{Hq},{Dv}]" in txt


@pytest.mark.parametrize("T,S", [(32, 256), (256, 4096), (256, 16512)])
def test_the_latent_flash_form_compiles_at_the_cells_shapes(v5e, T, S):
    """A chunk's absorbed form: every (token, head) a query row against the
    one shared row a key, key blocks of up to 512."""
    bf, i32 = jnp.bfloat16, jnp.int32
    txt = _compiled_text(
        lambda q, ql, k, v, qp, kp, kv: A.flash_attention(
            q, k, v, qp, kp, kv, interpret=False, scale=0.1147, latent=ql),
        _sds(v5e, (1, T, 128, 128), bf), _sds(v5e, (1, T, 128, 512), bf),
        _sds(v5e, (1, S, 1, 128), bf), _sds(v5e, (1, S, 1, 512), bf),
        _sds(v5e, (1, T), i32), _sds(v5e, (1, S), i32),
        _sds(v5e, (1, S), jnp.bool_))
    assert txt.count('custom_call_target="tpu_custom_call"') == 1


def test_decode_step_compiles_with_kernels_for_v5e(v5e):
    """One whole decode step at llama-3.2-1b widths (depth cut to two layers
    to stay within seconds), placed on the described device through a mesh:
    the model picks compiled kernels from the mesh's device, so the program
    must hold one ``tpu_custom_call`` per layer — in a CPU-backend process
    keyed on the default backend it would hold none."""
    from dynamo_tpu.parallel.mesh import serving_mesh

    cfg = llama.preset("llama-3.2-1b", num_layers=2)
    mesh = serving_mesh(1, devices=[v5e])
    B, P = 32, 34
    n_pages = B * P + 1
    pshapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: _sds(v5e, a.shape, a.dtype), pshapes)
    pool = _sds(v5e, (cfg.num_layers, cfg.num_kv_heads, n_pages, PAGE,
                      cfg.head_dim), cfg.dtype)
    txt = _compiled_text(
        lambda p, t, k, v, pt, ln: llama.forward_decode(
            p, cfg, t, k, v, pt, ln, attn_impl="pallas", mesh=mesh),
        params, _sds(v5e, (B,), jnp.int32), pool, pool,
        _sds(v5e, (B, P), jnp.int32), _sds(v5e, (B,), jnp.int32))
    assert txt.count("tpu_custom_call") >= cfg.num_layers


def _pool_relayouts(txt: str, pool_shape) -> dict:
    """Lines of a compiled program that materialise a pool-sized or a
    layer-of-the-pool-sized array by ``copy`` or ``slice``: what the chip
    showed as 46-62 % of device time before PR 26 (PERF.md §6)."""
    import re

    whole = ",".join(map(str, pool_shape))
    layer = ",".join(map(str, pool_shape[1:]))
    lines = [ln for ln in txt.splitlines()
             if re.search(r" (copy|slice)\(", ln)]
    return {"pool": [ln for ln in lines
                     if re.search(r"= \w+\[%s\]" % whole, ln)],
            "layer": [ln for ln in lines
                      if re.search(r"= \w+\[(1,)?%s\]" % layer, ln)]}


@pytest.mark.parametrize("geom", GEOMETRIES[:2],
                         ids=lambda g: "x".join(map(str, g)))
def test_paged_whole_pool_by_layer_compiles_for_v5e(v5e, geom):
    """The form forward_decode calls: the whole [L, Hkv, n_pages, page, Dh]
    pool and a traced layer index. At Dh = 128 the custom call takes the
    pool itself (no pool-shaped operand is produced by a copy); at Dh = 64
    the fold re-lays one layer's slice, never the pool."""
    Hq, Hkv, Dh = geom
    L, B, P = 4, 32, 34
    n_pages = B * P + 1
    txt = _compiled_text(
        lambda q, k, v, pt, ln, l: A.paged_attention(
            q, k, v, pt, ln, l, interpret=False),
        _sds(v5e, (B, Hq, Dh), jnp.bfloat16),
        _sds(v5e, (L, Hkv, n_pages, PAGE, Dh), jnp.bfloat16),
        _sds(v5e, (L, Hkv, n_pages, PAGE, Dh), jnp.bfloat16),
        _sds(v5e, (B, P), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (), jnp.int32))
    assert "tpu_custom_call" in txt
    found = _pool_relayouts(txt, (L, Hkv, n_pages, PAGE, Dh))
    assert not found["pool"] and (Dh < 128 or not found["layer"]), found


@pytest.mark.parametrize("program", ["decode_scan", "prefill_chunk",
                                     "prefill_chunk_rows",
                                     "prefill_chunk_by_page"])
def test_bucket_program_bodies_keep_the_pool_as_stored(v5e, program):
    """The decode scan and the prefill chunk at Dh = 128 widths (qwen2-1.5b,
    depth cut to two layers; the chunk's context read by row and by page,
    its new rows written by row and, ``by_page``, a page run at a time): the
    donated pools are updated in place — no whole-pool copy at entry or
    exit, no per-layer slice re-laid for the kernel — and come back aliased
    to their inputs."""
    from dynamo_tpu.parallel.mesh import serving_mesh

    cfg = llama.preset("qwen2-1.5b", num_layers=2)
    mesh = serving_mesh(1, devices=[v5e])
    B, S, C, N = 32, 1152, 128, 4
    P = S // PAGE
    pshape = (cfg.num_layers, cfg.num_kv_heads, 1089, PAGE, cfg.head_dim)
    pshapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: _sds(v5e, a.shape, a.dtype), pshapes)
    pool = _sds(v5e, pshape, cfg.dtype)

    def decode_scan(p, t, k, v, pt, ln):
        def one(carry, _):
            t, ln, k, v = carry
            lg, k, v = llama.forward_decode(p, cfg, t, k, v, pt, ln,
                                            attn_impl="pallas", mesh=mesh)
            return (jnp.argmax(lg[:, 0], -1).astype(jnp.int32), ln + 1,
                    k, v), None
        (t, ln, k, v), _ = jax.lax.scan(one, (t, ln, k, v), None, length=N)
        return t, k, v

    def prefill_chunk(p, t, pos, k, v, wi, ri, rp, rv, li):
        pages = None if program.endswith("rows") else ri[:, ::PAGE] // PAGE
        runs = wi[:, ::PAGE] // PAGE if program.endswith("by_page") else None
        return llama.forward(p, cfg, t, pos, k, v, wi, ri, rp, rv,
                             attn_impl="flash", mesh=mesh, logits_idx=li,
                             read_pages=pages, write_pages=runs)

    i32 = jnp.int32
    if program == "decode_scan":
        fn, donate = decode_scan, (2, 3)
        args = (params, _sds(v5e, (B,), i32), pool, pool,
                _sds(v5e, (B, P), i32), _sds(v5e, (B,), i32))
    else:
        fn, donate = prefill_chunk, (3, 4)
        args = (params, _sds(v5e, (1, C), i32), _sds(v5e, (1, C), i32),
                pool, pool, _sds(v5e, (1, C), i32), _sds(v5e, (1, S), i32),
                _sds(v5e, (1, S), i32), _sds(v5e, (1, S), jnp.bool_),
                _sds(v5e, (1,), i32))
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    found = _pool_relayouts(compiled.as_text(), pshape)
    assert not found["pool"] and not found["layer"], found
    ma = compiled.memory_analysis()
    pool_bytes = 2 * jnp.dtype(cfg.dtype).itemsize
    for d in pshape:
        pool_bytes *= d
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.temp_size_in_bytes < pool_bytes // 8


def _weight_copies(txt: str, published) -> list:
    """Lines of a compiled program whose RESULT has the dimensions of a q /
    k / v projection (``published``: the stacks [L, D, H, Dh]), whole or
    one layer of it, as published or with the heads merged ([H x Dh, D]),
    and which is a ``copy``, a ``transpose`` or a fusion: no step of
    inference computes a tensor of a weight's shape, so such an operation
    can only re-lay the weight or cut a layer out of its stack (a tenth of
    the device time of mistral-7b-16l.longprompt before PR 50). The
    compiler's asynchronous prefetch into fast memory (``copy-start`` /
    ``slice-start`` and their ``-done``) is not one, nor is an instruction
    INSIDE a fusion (a matmul's fusion names its operand through a bitcast
    fusion of the weight's shape: the operand is read where it lies)."""
    import re

    dims = set()
    for L, D, H, Dh in published:
        for layer in ((D, H, Dh), (H * Dh, D)):
            layer = ",".join(map(str, layer))
            dims |= {layer, "1," + layer, f"{L},{layer}"}
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.\-]+)", txt))
    found, inside = [], False
    for ln in txt.splitlines():
        if ln[:1] in "%E":                      # a computation's header
            inside = ln.split(" ", 1)[0] in fused
        elif (not inside
              and (m := re.search(r"= (.*?) (copy|transpose|fusion)\(", ln))
              and set(re.findall(r"\w+\[([\d,]+)\]", m.group(1))) & dims):
            found.append(ln)
    return found


# (lanes, chunk, context, pages): the two dense cells' programs
WEIGHT_CASES = {"mistral-7b": (16, 256, 2176, 529),
                "qwen2-1.5b": (32, 128, 1152, 1089)}


@pytest.mark.parametrize("preset,program,tree", [
    ("mistral-7b", "decode_scan", "stored"),
    ("mistral-7b", "prefill_chunk", "stored"),
    ("qwen2-1.5b", "decode_scan", "stored"),
    ("qwen2-1.5b", "prefill_chunk", "stored"),
    ("mistral-7b", "decode_scan", "published")])
def test_no_bucket_program_copies_a_weight(v5e, preset, program, tree):
    """The decode scan and the page-run prefill chunk at the two dense
    cells' widths (depth four) on the tree an engine stores
    (``llama.stored_params``: ``wq`` / ``wk`` / ``wv`` a matrix [H x Dh, D]
    a layer) hold no operation that produces a weight-shaped array. The
    fifth case is the finding the other way round, so that the test is known
    to see what it guards: on the published tree the decode scan at
    mistral-7b's widths copies the WHOLE ``wq`` stack, ``copy
    bf16[L,4096,32,128]``, once a dispatch (1.6 ms of a dispatch on the
    chip, PERF.md section 6, PR 50)."""
    from dynamo_tpu.parallel.mesh import serving_mesh

    cfg = llama.preset(preset, num_layers=4)
    mesh = serving_mesh(1, devices=[v5e])
    B, C, S, n_pages = WEIGHT_CASES[preset]
    P = S // PAGE
    published = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    shapes = (published if tree == "published"
              else jax.eval_shape(llama.stored_params, published))
    params = jax.tree.map(lambda a: _sds(v5e, a.shape, a.dtype), shapes)
    pool = _sds(v5e, (cfg.num_layers, cfg.num_kv_heads, n_pages, PAGE,
                      cfg.head_dim), cfg.dtype)
    i32 = jnp.int32

    def decode_scan(p, t, k, v, pt, ln):
        def one(carry, _):
            t, ln, k, v = carry
            lg, k, v = llama.forward_decode(p, cfg, t, k, v, pt, ln,
                                            attn_impl="pallas", mesh=mesh)
            return (jnp.argmax(lg[:, 0], -1).astype(i32), ln + 1, k, v), None
        (t, ln, k, v), _ = jax.lax.scan(one, (t, ln, k, v), None, length=4)
        return t, k, v

    def prefill_chunk(p, t, pos, k, v, wi, ri, rp, rv, li):
        return llama.forward(p, cfg, t, pos, k, v, wi, ri, rp, rv,
                             attn_impl="flash", mesh=mesh, logits_idx=li,
                             read_pages=ri[:, ::PAGE] // PAGE,
                             write_pages=wi[:, ::PAGE] // PAGE)

    if program == "decode_scan":
        fn, donate = decode_scan, (2, 3)
        args = (params, _sds(v5e, (B,), i32), pool, pool,
                _sds(v5e, (B, P), i32), _sds(v5e, (B,), i32))
    else:
        fn, donate = prefill_chunk, (3, 4)
        args = (params, _sds(v5e, (1, C), i32), _sds(v5e, (1, C), i32),
                pool, pool, _sds(v5e, (1, C), i32), _sds(v5e, (1, S), i32),
                _sds(v5e, (1, S), i32), _sds(v5e, (1, S), jnp.bool_),
                _sds(v5e, (1,), i32))
    txt = jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()
    found = _weight_copies(
        txt, [published["layers"][w].shape for w in llama.ATTN_IN])
    if tree == "stored":
        assert not found, found
    else:
        assert any(" copy(" in ln and "bf16[4,4096,32,128]" in ln
                   for ln in found), found


def test_the_decode_steps_rows_are_written_by_the_kernel(v5e, monkeypatch):
    """qwen2-1.5b's decode step at 32 lanes, all 28 layers: with the write
    in the paged kernel the program holds no scatter into the pools
    (``bf16[28,2,...,64,128]``: 56 of them a step before), every pool-shaped
    value is the donated parameter or a kernel's aliased result, nothing
    pool-sized is copied, and the program's temporaries are no larger than
    with ``kv_write`` but for ONE re-laid ``wk`` (22 MB, once a dispatch). The
    program with ``kv_write`` re-lays ``wk`` and ``wv`` too, into the chip's
    fast memory, which ``temp_size_in_bytes`` does not count; this one re-lays
    ``wk`` into HBM, which it does (read on the chip as 11.1 against 30.5 MB:
    PERF.md section 6, PR 38)."""
    import re

    from dynamo_tpu.parallel.mesh import serving_mesh

    cfg = llama.preset("qwen2-1.5b")
    mesh = serving_mesh(1, devices=[v5e])
    B, S = 32, 1152
    pshape = (cfg.num_layers, cfg.num_kv_heads, 1089, PAGE, cfg.head_dim)
    pshapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: _sds(v5e, a.shape, a.dtype), pshapes)
    pool, i32 = _sds(v5e, pshape, cfg.dtype), jnp.int32
    args = (params, _sds(v5e, (B,), i32), pool, pool,
            _sds(v5e, (B, S // PAGE), i32), _sds(v5e, (B,), i32))

    def compiled():
        return jax.jit(
            lambda p, t, k, v, pt, ln: llama.forward_decode(
                p, cfg, t, k, v, pt, ln, attn_impl="pallas", mesh=mesh),
            donate_argnums=(2, 3)).lower(*args).compile()

    assert llama.kernel_writes(mesh, "pallas", cfg.k_store_dim, cfg.kv_fold)
    kernel = compiled()
    monkeypatch.setattr(llama, "kernel_writes", lambda *a: False)
    scatter = compiled()

    whole = ",".join(map(str, pshape))
    made = {"kernel": {}, "scatter": {}}
    for how, c in (("kernel", kernel), ("scatter", scatter)):
        for op in re.findall(r"= \(?\w+\[%s\][^=]*? ([\w\-]+)\(" % whole,
                             c.as_text()):
            made[how][op] = made[how].get(op, 0) + 1
    assert made["scatter"].get("scatter") == 2 * cfg.num_layers, made
    assert set(made["kernel"]) <= {"parameter", "get-tuple-element",
                                   "custom-call", "bitcast"}, made
    txt = kernel.as_text()
    assert txt.count('custom_call_target="tpu_custom_call"') == cfg.num_layers
    found = _pool_relayouts(txt, pshape)
    assert not found["pool"] and not found["layer"], found
    pool_bytes = 2 * jnp.dtype(cfg.dtype).itemsize
    for d in pshape:
        pool_bytes *= d
    ma, was = kernel.memory_analysis(), scatter.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes
    wk = pshapes["layers"]["wk"]
    assert ma.temp_size_in_bytes <= (
        was.temp_size_in_bytes + wk.size * wk.dtype.itemsize + (1 << 20))


def test_the_samplers_window_stays_a_branch_on_the_v5e(v5e):
    """``sample`` at qwen2's head shape inside a decode scan: the v5e
    compiler keeps the ``lax.cond`` a real ``conditional`` (it does not
    compute both branches and select), and ``TopK`` over the vocabulary is
    issued inside the branch alone, so an all-greedy dispatch never runs it."""
    import re

    from dynamo_tpu.engine.sampling import sample

    B, V = 32, 151936
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), B))

    def steps(logits, temp, top_p, top_k, key, active):
        def one(carry, _):
            lg, key = carry
            tok, logp, key = sample(lg, temp, top_p, top_k, key, active)
            return (lg + logp[:, None], key), tok
        return jax.lax.scan(one, (logits, key), None, length=2)

    txt = _compiled_text(
        steps, _sds(v5e, (B, V), jnp.float32), _sds(v5e, (B,), jnp.float32),
        _sds(v5e, (B,), jnp.float32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, keys.shape, keys.dtype), _sds(v5e, (B,), jnp.bool_))
    assert re.search(r"= .* conditional\(", txt)
    topk = [ln for ln in txt.splitlines() if 'custom_call_target="TopK"' in ln]
    assert topk and all("/cond/branch_1_fun/" in ln for ln in topk), topk


def test_the_routed_decode_layer_holds_both_forms_on_the_v5e(v5e):
    """A routed layer of the lfm2 cell's decode program (32 rows, 64 experts
    of 2048 x 1536, 4 a token, the stacked ``layer=`` form inside a scan)
    with the dispatch's ``active`` mask: the v5e compiler keeps the choice
    between sorted and dense a real ``conditional``, the ``ragged_dot``
    calls sit in one branch and the dense einsums in the other, and neither
    branch copies the stacked expert weights (7 GB: the program's
    temporaries stay a few MB)."""
    import re

    from dynamo_tpu.models import moe

    L, E, D, F, K, B = 6, 64, 2048, 1536, 4, 32
    assert moe.dispatch_form(B, K, E, masked=True) == "by_hit"

    def layers(x, active, wr, wg, wu, wd):
        def body(x, l):
            y, hit, _ = moe.moe_ffn(x, wr[l], wg, wu, wd, K, layer=l,
                                    active=active)
            return x + y, hit
        return jax.lax.scan(body, x, jnp.arange(L))

    bf = jnp.bfloat16
    compiled = jax.jit(layers).lower(
        _sds(v5e, (B, 1, D), bf), _sds(v5e, (B,), jnp.bool_),
        _sds(v5e, (L, D, E), bf), _sds(v5e, (L, E, D, F), bf),
        _sds(v5e, (L, E, D, F), bf), _sds(v5e, (L, E, F, D), bf)).compile()
    txt = compiled.as_text()
    branches = re.search(
        r"= .* conditional\(.*branch_computations=\{([^}]*)\}", txt)
    assert branches, "no conditional"
    dense, sorted_ = branches.group(1).replace(" ", "").split(",")
    holder, holds = None, []          # the computation of each ragged-dot
    for ln in txt.splitlines():
        if ln[:1] in "%E" and ln.rstrip().endswith("{"):
            holder = ln.split(" ")[1 if ln.startswith("ENTRY") else 0]
        elif re.match(r"\s+%ragged-dot\S* = .* custom-call\(", ln):
            holds.append(holder)
    assert holds and set(holds) == {sorted_} != {dense}, (holds, branches)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("full_tracebacks,limit,same", [
    (False, 10, True), (True, 10, False), (True, 0, True)],
    ids=["outermost-name", "jax-default", "name-stack-no-frames"])
def test_kernel_program_text_vs_call_stack(v5e, full_tracebacks, limit, same):
    """A Pallas kernel is serialised into its program with its locations.
    With jax's default (full tracebacks, ten frames) those hold the tracing
    call stack, so the same program traced from two entry points hashes to
    two compile cache keys. With what init_compile_cache sets (the last
    case: the name stack and no frame, and the names part of the key) it is
    one program, locations and all, as it was without them under the
    outermost name alone (the first, until PR 39)."""
    args = (_sds(v5e, (8, 32, 64), jnp.bfloat16),
            _sds(v5e, (8, 65, PAGE, 64), jnp.bfloat16),
            _sds(v5e, (8, 65, PAGE, 64), jnp.bfloat16),
            _sds(v5e, (8, 8), jnp.int32), _sds(v5e, (8,), jnp.int32))

    def lowered_text(depth):
        if depth:
            return lowered_text(depth - 1)

        def fresh(q, k, v, pt, ln):        # a new function: a new trace
            return A.paged_attention(q, k, v, pt, ln, interpret=False)

        # (with debug information where it counts in the cache's key)
        return jax.jit(fresh).lower(*args).compiler_ir().operation.get_asm(
            enable_debug_info=ours)

    asked = {"jax_include_full_tracebacks_in_locations": full_tracebacks,
             "jax_traceback_in_locations_limit": limit}
    ours = same and full_tracebacks
    if ours:
        asked["jax_compilation_cache_include_metadata_in_key"] = True
        assert asked == jaxenv.PROGRAM_LOCATIONS
    was = {k: getattr(jax.config, k) for k in asked}
    for k, v in asked.items():
        jax.config.update(k, v)
    try:
        assert (lowered_text(0) == lowered_text(3)) is same
    finally:
        for k, v in was.items():
            jax.config.update(k, v)


# ---------------------------------------------------------------------------
# device decisions that used to fall back silently
# ---------------------------------------------------------------------------

def _tiny_engine_cfg(**kw):
    from dynamo_tpu.engine.engine import JaxEngineConfig

    return JaxEngineConfig(**{"model": llama.preset("tiny-byte"),
                              "max_batch": 2, "max_context": 64,
                              "page_size": 8, "prefill_chunk": 32, **kw})


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,expect", [
    ("tpu", "TPU v5 lite", True),
    ("renamed-backend", "TPU v5 lite", True),   # keyed on the device kind
    ("cpu", "cpu", False),
    ("gpu", "NVIDIA H100", False),
])
def test_on_tpu_is_keyed_on_the_device(platform, kind, expect):
    assert jaxenv.on_tpu(_FakeDevice(platform, kind)) is expect


def test_on_tpu_defaults_to_this_process_device():
    assert jaxenv.on_tpu() is False        # the suite runs on the CPU


@pytest.mark.parametrize("kind,platform", [
    ("TPU vNext", "tpu"), ("NVIDIA H100", "gpu"), ("TPU vNext", "renamed")])
def test_detect_peaks_raises_for_unknown_accelerator(monkeypatch, kind,
                                                     platform):
    monkeypatch.delenv("DYN_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("DYN_PEAK_GBPS", raising=False)
    with pytest.raises(ValueError, match=kind):
        roofline.detect_peaks(kind, platform)


def test_detect_peaks_known_kinds_and_cpu(monkeypatch):
    monkeypatch.delenv("DYN_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("DYN_PEAK_GBPS", raising=False)
    assert roofline.detect_peaks("TPU v5 lite", "tpu").source == \
        "table:v5 lite"
    assert roofline.detect_peaks("cpu", "cpu").source == "calibrated-cpu"


def test_auto_on_tpu_with_failing_probe_raises(monkeypatch):
    """``attn_impl="auto"`` on a TPU resolves to pallas or raises the
    compiler's message; it never degrades to dense attention. The TPU is
    mocked; the probe is real and cannot compile on the CPU backend."""
    from dynamo_tpu.engine import engine as E

    monkeypatch.setattr(E, "on_tpu", lambda device=None: True)
    # the mocked platform would otherwise also fail the peak-table lookup
    monkeypatch.setenv("DYN_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("DYN_PEAK_GBPS", "100")
    with pytest.raises(ValueError, match="interpret mode"):
        E.EngineCore(_tiny_engine_cfg())


def test_auto_off_tpu_is_dense_and_reported():
    from dynamo_tpu.engine.engine import EngineCore

    core = EngineCore(_tiny_engine_cfg())
    assert (core.attn_impl, core.decode_attn_impl, core.paged_kernel) == \
        ("xla", "xla", None)


def test_explicit_pallas_off_tpu_reports_interpreted_kernel():
    """The one paged kernel, in the interpreter: the decode program the chip
    compiles, the kernel writing the rows where the pool is stored as it
    reads it (``benchmarks/harness/cell.py`` holds a cell to ``dma``)."""
    from dynamo_tpu.engine.engine import EngineCore

    core = EngineCore(_tiny_engine_cfg(
        model=llama.preset("tiny-byte", head_dim=128), attn_impl="pallas"))
    assert core.paged_kernel == "dma[interpret]"
    assert core.decode_kv_write == "kernel"


@pytest.mark.parametrize("model,impl,expect", [
    ({}, "pallas", "scatter"),           # rows of 16 stored unfolded
    ({"head_dim": 128}, "pallas", "kernel"),
    ({"head_dim": 256}, "pallas", "kernel"),
    ({"head_dim": 64, "kv_fold": 2}, "pallas", "kernel"),
    ({"kv_fold": 8}, "pallas", "kernel"),        # rows of 16, a page a row
    ({"head_dim": 64}, "pallas", "scatter"),     # 64-lane rows, unfolded
    ({"head_dim": 128}, "xla", "scatter"),       # the dense path
])
def test_the_engine_reports_what_writes_the_decode_rows(model, impl, expect):
    """``dyn_engine_info{decode_kv_write}`` is what ``forward_decode``'s own
    predicate says for the engine's pools, on a CPU as on the chip, and the
    label rides the gauge."""
    from dynamo_tpu.engine.engine import JaxEngine

    eng = JaxEngine(_tiny_engine_cfg(
        model=llama.preset("tiny-byte", **model), attn_impl=impl,
        warmup=False))
    try:
        assert eng.core.decode_kv_write == expect
        text = eng.core.stage.registry.render()
        assert f'decode_kv_write="{expect}"' in text
    finally:
        eng.shutdown()


def test_the_hot_path_reads_no_switch_from_the_environment():
    """What a program runs is decided by its arguments and its devices: the
    package reads two ``DYNAMO_TPU_*`` names, both in ``runtime/`` (which
    store and which data plane a process talks to), none in the modules a
    dispatch goes through."""
    import re

    root = os.path.dirname(os.path.dirname(          # dynamo_tpu/
        os.path.abspath(jaxenv.__file__)))
    read = {}
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    for var in re.findall(r"DYNAMO_TPU_[A-Z_]+", f.read()):
                        read.setdefault(var, set()).add(
                            os.path.relpath(folder, root))
    assert read == {"DYNAMO_TPU_STORE": {"runtime"},
                    "DYNAMO_TPU_DATAPLANE": {"runtime"}}


@pytest.mark.parametrize("n,align,expect", [
    (512, 8, 128), (32, 8, 32), (5, 8, 5), (3, 8, 3),      # query axis
    (2176, 128, 128), (256, 128, 128), (80, 128, 80),      # context axis
    (2112, 128, 2112)])
def test_pick_block_obeys_mosaic_tiling(n, align, expect):
    """A block is a multiple of the tile (8 sublanes / 128 lanes) that
    divides the axis, or the whole axis — the two shapes Mosaic accepts."""
    assert A._pick_block(n, align) == expect


@pytest.mark.parametrize("max_context,page", [(2048, 64), (1024, 16),
                                              (256, 16), (64, 8)])
def test_context_buckets_tile_for_flash(max_context, page):
    from dynamo_tpu.engine.engine import EngineCore

    core = EngineCore(_tiny_engine_cfg(max_context=max_context,
                                       page_size=page))
    assert core.s_buckets[-1] >= max_context + core._spec_pad
    for s in core.s_buckets:
        assert s % page == 0
        assert s <= 128 or s % 128 == 0


def test_compile_cache_dir_honours_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jaxenv.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jaxenv.compile_cache_dir() == os.path.join(repo, ".jax_cache")
    assert jaxenv.compile_cache_dir() == jaxenv.compile_cache_dir()


@pytest.mark.parametrize("env_set,platforms", [
    (True, "cpu"), (True, None), (False, None), (False, "tpu,cpu"),
    (False, "cpu")])
def test_init_compile_cache_sets_a_path_only_without_the_env(
        monkeypatch, tmp_path, env_set, platforms):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no cache path in
    code (jax reads the variable itself); unset, it sets the in-checkout
    path — except in a process told to run on the CPU, which keeps no
    persistent cache. No backend is touched either way."""
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(type(jax.config), "jax_platforms", platforms,
                        raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: pytest.fail(
        "init_compile_cache must not initialise a backend"))
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = jaxenv.init_compile_cache()
    if env_set:
        assert path == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
    elif platforms == "cpu":
        assert path is None and updates == {}
    else:
        assert path.endswith(".jax_cache")
        assert updates["jax_compilation_cache_dir"] == path
    if path is not None:
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
        assert updates["jax_include_full_tracebacks_in_locations"] is True
        assert updates["jax_traceback_in_locations_limit"] == 0
        assert updates["jax_compilation_cache_include_metadata_in_key"] is True


def test_cpu_env_roundtrip(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--foo=1")
    assert not jaxenv.cpu_env_ready(4)
    env = jaxenv.cpu_env(4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == \
        "--foo=1 --xla_force_host_platform_device_count=4"
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    assert jaxenv.cpu_env_ready(4) and jaxenv.cpu_env_ready(2)
    assert not jaxenv.cpu_env_ready(8)


def test_serve_auto_platform_reads_device_nodes_not_jax(monkeypatch):
    from dynamo_tpu.sdk import serve

    seen = []

    def fake_glob(pattern):
        seen.append(pattern)
        return ["/dev/vfio/0"] if "vfio" in pattern else []

    monkeypatch.setattr(serve.glob, "glob", fake_glob)
    monkeypatch.setenv("TPU_NAME", "")          # no longer consulted
    assert serve.host_has_tpu() is True
    monkeypatch.setattr(serve.glob, "glob", lambda pattern: [])
    monkeypatch.setenv("TPU_NAME", "local")
    assert serve.host_has_tpu() is False
    assert any("accel" in p for p in seen)


def test_allocator_confines_a_one_chip_worker():
    from dynamo_tpu.sdk.allocator import TpuAllocator

    a = TpuAllocator(total_chips=4, platform="tpu")
    envs = [a.allocate(1, service="Worker") for _ in range(4)]
    assert [e["TPU_VISIBLE_DEVICES"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_ADDRESSES"].endswith(e["TPU_PROCESS_PORT"])
