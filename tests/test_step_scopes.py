"""Every device operation says which part of the model it ran (compile only,
the CPU): for a tiny dense, a routed, an indexed, a per-kind and a state
model, every ``op_name`` of the engine's own compiled decode step and prefill
chunk names a scope of ``llama.SCOPES``, each scope such a model uses
appears, a Pallas kernel lowered for a described TPU carries its scope in its
location, and the benchmark's reader groups exactly the program's scopes."""

import base64
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import scopes
from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
from dynamo_tpu.models import llama
from dynamo_tpu.utils import jaxenv, roofline
from tests.test_command_a_plus import TINY as COMMAND_A
from tests.test_granite_hybrid import TINY as GRANITE
from tests.test_longcat_flash import TINY as LONGCAT
from tests.test_mimo_v2_flash import TINY as MIMO

EVERY = {"embed", "attn_in", "kv_write", "attn_out", "ffn", "head", "sample"}
MODELS = {
    "dense": (lambda: llama.preset("tiny-byte"), EVERY | {"attn"}),
    "routed": (lambda: llama.preset("tiny-moe"),
               EVERY | {"attn", "moe_ffn"}),
    "indexed": (lambda: llama.preset("tiny-keye"),
                EVERY | {"attn", "moe_ffn", "index_select"}),
    "per-kind": (lambda: llama.LlamaConfig.from_hf_config(MIMO),
                 EVERY | {"attn", "attn_full", "attn_window", "moe_ffn"}),
    "state": (lambda: llama.LlamaConfig.from_hf_config(GRANITE),
              EVERY | {"attn", "ssm_in", "ssm_out"}),
    # the same through the kernels (the Pallas interpreter here): what the
    # paged kernel and the state kernel lower to carries their scopes
    "state-kernels": (lambda: llama.LlamaConfig.from_hf_config(GRANITE),
                      EVERY | {"attn", "ssm_in", "ssm_out"}, "pallas"),
    # two sublayers a layer and a routed branch across them: the branch and
    # its identity part under ``moe_ffn``, the dense feed-forward beside it
    # and the landing add under ``ffn``
    "two-sublayers": (lambda: llama.LlamaConfig.from_hf_config(LONGCAT),
                      EVERY | {"attn", "moe_ffn"}),
    # a parallel block: the one norm under ``attn_in``, router and routed
    # experts under ``moe_ffn``, the shared experts and the block's one
    # residual add under ``ffn``; no scope of its own
    "parallel-block": (lambda: llama.LlamaConfig.from_hf_config(COMMAND_A),
                       EVERY | {"attn", "attn_full", "attn_window",
                                "moe_ffn"}),
}
KIND_SCOPE = {"decode": "ssm_step", "prefill": "ssm_scan"}
# instructions that hold others or move nothing: a trace attributes LEAF
# operations, and these carry the name of the loop or branch they are
NOT_LEAVES = re.compile(
    r" (while|conditional|call|parameter|constant|tuple|"
    r"get-tuple-element|bitcast|after-all|partition-id)\(")


@pytest.fixture(autouse=True)
def program_locations():
    """What every engine entry point sets (a CPU test process calls none)."""
    was = {k: getattr(jax.config, k) for k in jaxenv.PROGRAM_LOCATIONS}
    for k, v in jaxenv.PROGRAM_LOCATIONS.items():
        jax.config.update(k, v)
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def compiled_programs(model, monkeypatch, impl="xla"):
    """-> {kind: compiled text} of the bucket programs one short request
    runs through the engine, as the engine itself calls them."""
    texts = {}

    def spy(kind, fn, record):
        def call(*args, **kw):
            if kind not in texts:
                texts[kind] = fn.lower(*args, **kw).compile().as_text()
            return fn(*args, **kw)
        return call

    monkeypatch.setattr(roofline, "instrument_compile", spy)
    core = EngineCore(JaxEngineConfig(
        model=model, attn_impl=impl, page_size=16, max_batch=2,
        max_context=64, prefill_chunk=16, decode_steps=2))
    core.submit("r", BackendInput(token_ids=list(range(3, 23)),
                                  stop=StopConditions(max_tokens=4)))
    for _ in range(50):
        if any(so.finish is not None for so in core.step()):
            break
    assert set(texts) == {"prefill", "decode"}
    return texts


def loops_own(op_name: str) -> bool:
    """Is this what ``lax.scan`` itself adds around its body (the counter,
    its test, the slice of a scanned input, the stacking of a step's
    outputs: one primitive directly in a ``while``), or what the compiler
    adds in the name of the body's call as a whole? No scope can hold those;
    on the device they are scalar work and read as ``unscoped``."""
    tail = re.split(r"while/(?:body|cond)/", op_name)[-1]
    return tail in ("add", "lt", "dynamic_slice", "dynamic_update_slice",
                    "closed_call")


@pytest.mark.parametrize("name", sorted(MODELS))
def test_every_operation_of_a_bucket_program_names_a_scope(name, monkeypatch):
    make, uses, *impl = MODELS[name]
    for kind, text in compiled_programs(make(), monkeypatch, *impl).items():
        seen, unscoped = set(), []
        for line in text.splitlines():
            m = re.search(r'op_name="([^"]*)"', line)
            # (a reducer's or comparator's body is lowered on its own, with
            # bare names, and is no operation of its own on the device)
            if (not m or NOT_LEAVES.search(line)
                    or not m.group(1).startswith("jit(")):
                continue
            where = scopes.scope_of(m.group(1))
            if where is None:
                if not loops_own(m.group(1)):
                    unscoped.append(line.strip()[:200])
            else:
                assert where in llama.SCOPES, line
                seen.add(where.removeprefix("dynamo."))
        assert not unscoped, (kind, unscoped[:8])
        want = uses | ({KIND_SCOPE[kind]} if name.startswith("state")
                       else set())
        if impl and kind == "decode":
            want -= {"kv_write"}     # the paged kernel writes the new rows
        assert seen == want, (kind, seen ^ want)


def test_the_readers_groups_hold_exactly_the_programs_scopes():
    grouped = [s for g in scopes.GROUPS.values() for s in g]
    assert len(grouped) == len(set(grouped))
    assert {scopes.PREFIX + s for s in grouped} == set(llama.SCOPES)
    assert len(set(llama.SCOPES)) == len(llama.SCOPES)
    with pytest.raises(ValueError):
        llama.scope("nowhere")


def test_a_kernel_lowered_for_a_tpu_names_its_scope_and_no_frame():
    """The decode step of llama-3.2-1b (two layers) lowered, not compiled,
    for a described v5e with the paged kernel: each layer's ``pallas_call``
    carries ``dynamo.attn`` in its location, and the location is the name
    stack alone (no file, no line: nothing of who traced it)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.parallel.mesh import serving_mesh

    try:
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this image
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=SingleDeviceSharding(dev))
    cfg = llama.preset("llama-3.2-1b", num_layers=2)
    mesh = serving_mesh(1, devices=[dev])
    B, P, page = 8, 4, 64
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes)
    pool = sds((cfg.num_layers, cfg.num_kv_heads, B * P + 1, page,
                cfg.head_dim), cfg.dtype)
    lowered = jax.jit(lambda p, t, k, v, pt, ln: llama.forward_decode(
        p, cfg, t, k, v, pt, ln, attn_impl="pallas", mesh=mesh)).lower(
        params, sds((B,), jnp.int32), pool, pool, sds((B, P), jnp.int32),
        sds((B,), jnp.int32))
    asm = lowered.compiler_ir().operation.get_asm(enable_debug_info=True)
    kernels = re.findall(r'loc\("([^"]*pallas_call)"(.?)', asm)
    assert kernels
    for name, behind in kernels:
        assert scopes.scope_of(name) == "dynamo.attn", name
        assert behind == ")", (name, behind)      # no frame behind the name
    # compiled, the kernel keeps the instruction name every reader of a
    # trace finds it by (ops/attention.py _KERNEL_NAME), scope and all
    calls = [ln for ln in lowered.compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == cfg.num_layers
    for ln in calls:
        assert ln.strip().removeprefix("ROOT ").startswith(
            "%tpu_custom_call"), ln[:80]
        assert scopes.scope_of(re.search(
            r'op_name="([^"]*)"', ln).group(1)) == "dynamo.attn", ln[-200:]
    assert ".py" not in "".join(re.findall(r"#loc\d* = loc\(.*", asm))
    # nor in the kernel's own serialised body, which is part of the compile
    # cache's key: no path of the checkout, no line of ops/attention.py
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    bodies = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                        lowered.as_text())
    assert bodies
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        for body in bodies:
            text = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                enable_debug_info=True)
            assert "#loc" in text and ".py" not in text


def test_ragged_dot_is_called_under_the_experts_scope_alone(monkeypatch):
    """XLA's rewriter of ``lax.ragged_dot`` names its custom calls itself
    (``ragged-dot-none``) and drops the instruction's scope, so the reader
    files them by that name (``scopes.EXPANDED``): sound while every
    ``ragged_dot`` of a bucket program is traced under that one scope."""
    from dynamo_tpu.models import moe

    assert set(scopes.EXPANDED.values()) == {"dynamo.moe_ffn"}
    monkeypatch.setattr(moe, "sorted_wins", lambda *a, **k: True)
    cfg = llama.preset("tiny-moe")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B, P, page = 2, 2, 16
    pool = jnp.zeros((cfg.num_layers, cfg.num_kv_heads, B * P + 1, page,
                      cfg.head_dim), cfg.dtype)
    jaxpr = jax.make_jaxpr(lambda p, t, k, v, pt, ln: llama.forward_decode(
        p, cfg, t, k, v, pt, ln))(
        params, jnp.zeros((B,), jnp.int32), pool, pool,
        jnp.ones((B, P), jnp.int32), jnp.ones((B,), jnp.int32))

    def stacks(jp, above=""):
        for eqn in jp.eqns:
            here = f"{above}/{eqn.source_info.name_stack}"
            if "ragged_dot" in eqn.primitive.name:
                yield here
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from stacks(sub, here)

    found = list(stacks(jaxpr.jaxpr))
    assert len(found) == 3 * cfg.num_layers, found
    assert all(scopes.scope_of(s) == "dynamo.moe_ffn" for s in found), found
