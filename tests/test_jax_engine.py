"""Engine-level tests: continuous batching, determinism, cancellation, TP."""

import asyncio
import time

import numpy as np
import pytest

from dynamo_tpu.engine.engine import EngineCore, JaxEngine, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import (
    BackendInput,
    EngineOutput,
    FinishReason,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.runtime.engine import Context


def make_cfg(**kw):
    d = dict(model=llama.preset("tiny-byte"), tp=1, page_size=8, max_batch=4,
             max_context=128, prefill_chunk=32)
    d.update(kw)
    return JaxEngineConfig(**d)


def req(tokens, max_tokens=8, **kw):
    return BackendInput(token_ids=list(tokens),
                        stop=StopConditions(max_tokens=max_tokens),
                        **kw)


def drain(core, want_seqs):
    """Step the core until all sequences in want_seqs have finished."""
    got = {s: [] for s in want_seqs}
    done = set()
    for _ in range(500):
        for so in core.step():
            got[so.seq_id].append(so)
            if so.finish is not None:
                done.add(so.seq_id)
        if done >= set(want_seqs):
            return got
    raise AssertionError(f"not all finished: {done} vs {want_seqs}")


@pytest.fixture(scope="module")
def core():
    return EngineCore(make_cfg())


@pytest.fixture(scope="module")
def engine():
    """One ``JaxEngine`` (engine thread and all) for every test that needs
    the async facade: its programs compile once."""
    eng = JaxEngine(make_cfg(max_batch=2))
    yield eng
    eng.shutdown()


def test_greedy_generate_and_finish(core):
    core.submit("a", req([5, 6, 7, 8], max_tokens=6))
    got = drain(core, ["a"])["a"]
    assert len(got) == 6
    assert got[-1].finish == FinishReason.LENGTH
    assert all(0 <= g.token < 259 for g in got)
    assert got[0].prompt_tokens == 4
    assert core.active == 0 and core.pool.free_pages == core.pool.num_pages - 1


def test_greedy_deterministic(core):
    core.submit("d1", req([9, 10, 11], max_tokens=5))
    t1 = [g.token for g in drain(core, ["d1"])["d1"]]
    core.submit("d2", req([9, 10, 11], max_tokens=5))
    t2 = [g.token for g in drain(core, ["d2"])["d2"]]
    assert t1 == t2


def test_batching_invariance(core):
    """Tokens generated for a request must not depend on its batchmates."""
    core.submit("solo", req([20, 21, 22, 23, 24], max_tokens=6))
    solo = [g.token for g in drain(core, ["solo"])["solo"]]
    core.submit("b1", req([20, 21, 22, 23, 24], max_tokens=6))
    core.submit("b2", req([50, 51], max_tokens=4))
    core.submit("b3", req([60, 61, 62, 63, 64, 65, 66, 67, 68], max_tokens=6))
    got = drain(core, ["b1", "b2", "b3"])
    assert [g.token for g in got["b1"]] == solo


def test_long_prompt_chunked_prefill(core):
    prompt = list(np.arange(70) % 250)  # > 2 prefill chunks of 32
    core.submit("long", req(prompt, max_tokens=3))
    got = drain(core, ["long"])["long"]
    assert len(got) == 3


def test_eos_stops(core):
    # find what greedy generates, then mark that token as EOS
    core.submit("p", req([30, 31, 32], max_tokens=4))
    toks = [g.token for g in drain(core, ["p"])["p"]]
    core.submit("e", BackendInput(
        token_ids=[30, 31, 32],
        stop=StopConditions(max_tokens=10),
        eos_token_ids=[toks[0]]))
    got = drain(core, ["e"])["e"]
    assert len(got) == 1 and got[0].finish == FinishReason.EOS
    # and ignore_eos overrides
    core.submit("i", BackendInput(
        token_ids=[30, 31, 32],
        stop=StopConditions(max_tokens=4, ignore_eos=True),
        eos_token_ids=[toks[0]]))
    got = drain(core, ["i"])["i"]
    assert len(got) == 4


def test_sampling_seeded_deterministic(core):
    r = lambda: BackendInput(
        token_ids=[40, 41, 42], stop=StopConditions(max_tokens=6),
        sampling=SamplingOptions(temperature=0.9, top_p=0.95, seed=1234))
    core.submit("s1", r())
    t1 = [g.token for g in drain(core, ["s1"])["s1"]]
    core.submit("s2", r())
    t2 = [g.token for g in drain(core, ["s2"])["s2"]]
    assert t1 == t2


def test_cancel_frees_slot(core):
    core.submit("c", req([5] * 20, max_tokens=100))
    for _ in range(3):
        core.step()
    core.cancel("c")
    outs = []
    for _ in range(5):
        outs.extend(core.step())
        if any(o.finish == FinishReason.CANCELLED for o in outs):
            break
    assert any(o.seq_id == "c" and o.finish == FinishReason.CANCELLED
               for o in outs)
    assert core.active == 0


def test_oversized_prompt_errors(core):
    core.submit("big", req(list(range(200)), max_tokens=1))  # > max_context 128
    outs = core.step()
    assert any(o.seq_id == "big" and o.finish == FinishReason.ERROR
               for o in outs)


def test_utilization_metrics(core):
    u = core.utilization()
    assert u["request_total_slots"] == 4.0
    assert u["kv_active_blocks"] == 0.0


def test_tp2_matches_tp1():
    import jax

    cfg1 = make_cfg(max_batch=2)
    cfg2 = make_cfg(max_batch=2, tp=2)
    c1 = EngineCore(cfg1, jax.devices()[:1])
    c2 = EngineCore(cfg2, jax.devices()[:2])
    c1.submit("x", req([10, 20, 30, 40], max_tokens=5))
    c2.submit("x", req([10, 20, 30, 40], max_tokens=5))
    t1 = [g.token for g in drain(c1, ["x"])["x"]]
    t2 = [g.token for g in drain(c2, ["x"])["x"]]
    assert t1 == t2


async def test_async_facade(engine):
    outs = []
    async for o in engine.generate(req([70, 71, 72], max_tokens=4),
                                   Context()):
        outs.append(o)
    assert sum(len(o.token_ids) for o in outs) == 4
    assert outs[-1].finish_reason == FinishReason.LENGTH


def test_unservable_prompt_rejected_not_starved():
    """A prompt that can never fit in the pool must error immediately and not
    block later requests (regression: head-of-line hang)."""
    cfg = make_cfg(max_batch=2, max_context=128, page_size=8)
    cfg.num_pages = 6  # 5 usable pages = 40 tokens max
    core = EngineCore(cfg)
    core.submit("huge", req(list(range(100)), max_tokens=2))
    core.submit("ok", req([1, 2, 3], max_tokens=2))
    got = drain(core, ["huge", "ok"])
    assert got["huge"][0].finish == FinishReason.ERROR
    assert got["ok"][-1].finish is not None


def test_decode_interleaves_with_long_prefill(core):
    """While a long prompt prefills chunk-by-chunk, an active decode keeps
    producing tokens (regression: prefill monopolized the engine)."""
    core.submit("dec", req([1, 2, 3], max_tokens=40))
    # get it decoding
    outs = []
    while not outs:
        outs = core.step()
    core.submit("long", req(list(range(100)), max_tokens=2))  # 4 chunks of 32
    long_first_token_seen = False
    decode_tokens_before_long_done = 0
    finished = set()
    for _ in range(300):
        outs = core.step()
        for so in outs:
            if so.seq_id == "dec" and not long_first_token_seen:
                decode_tokens_before_long_done += 1
            if so.seq_id == "long":
                long_first_token_seen = True
            if so.finish is not None:
                finished.add(so.seq_id)
        if long_first_token_seen:
            break
    # the decode stream must have advanced while "long" was prefilling
    assert decode_tokens_before_long_done > 0
    remaining = [s for s in ("dec", "long") if s not in finished]
    if remaining:
        drain(core, remaining)


def test_cum_logprob_accumulates(core):
    core.submit("lp", req([5, 6, 7], max_tokens=3))
    got = drain(core, ["lp"])["lp"]
    # cumulative: non-increasing sum of per-token logprobs (logp <= 0)
    assert got[0].logprob >= got[1].logprob >= got[2].logprob


def test_unaligned_max_context_correctness():
    """max_context not divisible by page_size must not corrupt KV via
    clamped page-table indexing (regression: floor-divided bucket widths)."""
    cfg_a = make_cfg(max_batch=1, page_size=16, max_context=40,
                     prefill_chunk=32)
    cfg_b = make_cfg(max_batch=1, page_size=16, max_context=48,
                     prefill_chunk=32)
    prompt = list(range(1, 29))
    ca, cb = EngineCore(cfg_a), EngineCore(cfg_b)
    ca.submit("x", req(prompt, max_tokens=8))
    cb.submit("x", req(prompt, max_tokens=8))
    ta = [g.token for g in drain(ca, ["x"])["x"]]
    tb = [g.token for g in drain(cb, ["x"])["x"]]
    assert ta == tb[:len(ta)]


def test_pool_pressure_defers_not_kills():
    """With the pool exhausted by batchmates, a nearly-done request waits for
    pages instead of dying with ERROR (regression: speculative reservation)."""
    cfg = make_cfg(max_batch=2, page_size=8, max_context=64)
    cfg.num_pages = 2 * ((64 + 8) // 8) + 1  # exactly 2 full seqs
    core = EngineCore(cfg)
    core.submit("a", req([1] * 30, max_tokens=20))
    core.submit("b", req([2] * 30, max_tokens=20))
    got = drain(core, ["a", "b"])
    assert got["a"][-1].finish == FinishReason.LENGTH
    assert got["b"][-1].finish == FinishReason.LENGTH


def test_pallas_tp2_matches_xla_tp2_logits():
    """The Pallas kernels run per-shard under shard_map at tp>1 (interpret
    mode on the CPU mesh): one decode step must match the dense-XLA path at
    the same tp to within bf16 accumulation noise, and a full generation
    must run (VERDICT round-1 weak #3: kernels were tp=1-only)."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from dynamo_tpu.parallel.mesh import tp_mesh

    m = llama.preset("tiny-byte")
    mesh = tp_mesh(2)
    specs = llama.param_specs(m, 2)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    params = jax.tree.map(lambda a, s: jax.device_put(a, s),
                          llama.init_params(m, jax.random.PRNGKey(0)),
                          shardings)
    B, page, Pg = 2, 8, 4
    n_pages = B * Pg + 1
    kv_sh = NamedSharding(mesh, llama.kv_cache_spec(m, 2))
    kp = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(7),
                          (m.num_layers, m.num_kv_heads, n_pages, page,
                           m.head_dim), jnp.float32).astype(m.dtype), kv_sh)
    vp = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(8), kp.shape,
                          jnp.float32).astype(m.dtype), kv_sh)
    tokens = jnp.asarray([5, 9], jnp.int32)
    pt = (jnp.arange(Pg, dtype=jnp.int32)[None]
          + jnp.arange(B, dtype=jnp.int32)[:, None] * Pg + 1)
    lengths = jnp.asarray([13, 27], jnp.int32)

    fx = jax.jit(partial(llama.forward_decode, cfg=m, attn_impl="xla"))
    fp = jax.jit(partial(llama.forward_decode, cfg=m, attn_impl="pallas",
                         mesh=mesh))
    lx, _, _ = fx(params, tokens=tokens, k_pool=kp, v_pool=vp,
                  page_tables=pt, lengths=lengths)
    lp, _, _ = fp(params, tokens=tokens, k_pool=kp, v_pool=vp,
                  page_tables=pt, lengths=lengths)
    assert float(jnp.abs(lx - lp).max()) < 0.05

    # and the engine end-to-end path compiles + generates at tp=2
    c2 = EngineCore(make_cfg(max_batch=2, tp=2, attn_impl="pallas"),
                    jax.devices()[:2])
    c2.submit("x", req([10, 20, 30, 40, 50], max_tokens=6))
    t2 = [g.token for g in drain(c2, ["x"])["x"]]
    assert len(t2) == 6 and all(0 <= t < 259 for t in t2)


def test_ring_prefill_engine_matches_xla():
    """attn_impl='ring' prefills through the sp mesh axis (sequence-parallel
    ring attention) and must match the plain xla engine for a prompt longer
    than one prefill chunk (VERDICT round-1 weak #4: ring was serving-dead)."""
    import jax

    prompt = list(range(2, 82))     # 80 tokens > prefill_chunk=32
    c1 = EngineCore(make_cfg(max_batch=2, attn_impl="xla"),
                    jax.devices()[:1])
    c2 = EngineCore(make_cfg(max_batch=2, sp=2, attn_impl="ring"),
                    jax.devices()[:2])
    c1.submit("r", req(prompt, max_tokens=6))
    c2.submit("r", req(prompt, max_tokens=6))
    t1 = [g.token for g in drain(c1, ["r"])["r"]]
    t2 = [g.token for g in drain(c2, ["r"])["r"]]
    assert t1 == t2


def test_ring_tp_combined_engine():
    """sp=2 x tp=2 mesh: ring prefill with head-sharded lanes + tp decode."""
    import jax

    prompt = list(range(3, 67))     # 64 tokens = 2 chunks
    c1 = EngineCore(make_cfg(max_batch=2, attn_impl="xla"),
                    jax.devices()[:1])
    c2 = EngineCore(make_cfg(max_batch=2, sp=2, tp=2, attn_impl="ring"),
                    jax.devices()[:4])
    c1.submit("rt", req(prompt, max_tokens=5))
    c2.submit("rt", req(prompt, max_tokens=5))
    t1 = [g.token for g in drain(c1, ["rt"])["rt"]]
    t2 = [g.token for g in drain(c2, ["rt"])["rt"]]
    assert t1 == t2


def test_moe_ep2_engine_matches_ep1():
    """A MoE model (tiny-moe preset) serves through the engine with the
    expert dimension sharded over ep=2, matching the unsharded tokens
    (VERDICT round-1 coverage gap: expert parallelism had no user)."""
    import jax

    cfg1 = make_cfg(model=llama.preset("tiny-moe"), max_batch=2)
    cfg2 = make_cfg(model=llama.preset("tiny-moe"), max_batch=2, ep=2)
    c1 = EngineCore(cfg1, jax.devices()[:1])
    c2 = EngineCore(cfg2, jax.devices()[:2])
    prompt = [11, 22, 33, 44]
    c1.submit("m", req(prompt, max_tokens=6))
    c2.submit("m", req(prompt, max_tokens=6))
    t1 = [g.token for g in drain(c1, ["m"])["m"]]
    t2 = [g.token for g in drain(c2, ["m"])["m"]]
    assert t1 == t2


def test_long_context_over_8k():
    """SURVEY 5.7: the long-context story must actually hold past 8k tokens —
    a 9000-token prompt prefills chunk-by-chunk through the paged pool and
    decodes correctly (tiny model dims keep CPU compile cheap; the sequence
    machinery — pages, chunking, position handling — is the real thing)."""
    cfg = make_cfg(
        model=llama.preset("tiny-byte", max_position=10240),
        max_batch=2, max_context=10240, page_size=64, prefill_chunk=1024)
    core = EngineCore(cfg)
    prompt = [(i * 7 + 3) % 251 for i in range(9001)]
    core.submit("long8k", req(prompt, max_tokens=4))
    got = drain(core, ["long8k"])["long8k"]
    assert len([so for so in got if so.finish is not None]) == 1
    toks = [so.token for so in got if so.token is not None]
    assert len(toks) == 4
    # chunk-size invariance of the prefill path is covered at small scale
    # by test_chunked_prefill_matches_full; here the point is that >8k
    # contexts run at all (pages, chunk loop, position handling)


async def test_logprobs_flow_to_openai_responses(engine):
    """Sampled-token logprobs must reach both OpenAI response shapes:
    completions (tokens/token_logprobs arrays) and chat (content entries)."""
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.pipeline import (build_chat_engine,
                                         build_completion_engine)
    from dynamo_tpu.llm.protocols.openai import (
        ChatCompletionRequest,
        CompletionRequest,
        aggregate_chat_chunks,
        aggregate_completion_chunks,
    )
    from dynamo_tpu.runtime.engine import collect

    card = ModelDeploymentCard(name="m")
    comp = build_completion_engine(card, "core", engine)
    req = CompletionRequest.from_dict({
        "model": "m", "prompt": "abcd", "max_tokens": 4, "logprobs": 1})
    chunks = await collect(comp.generate(req, Context()))
    agg = aggregate_completion_chunks([c for c in chunks
                                       if "event" not in c])
    lp = agg["choices"][0]["logprobs"]
    assert lp is not None
    assert len(lp["tokens"]) == len(lp["token_logprobs"]) == 4
    assert all(v <= 0.0 for v in lp["token_logprobs"])

    chat = build_chat_engine(card, "core", engine)
    creq = ChatCompletionRequest.from_dict({
        "model": "m", "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4, "logprobs": True})
    cchunks = await collect(chat.generate(creq, Context()))
    cagg = aggregate_chat_chunks([c for c in cchunks
                                  if "event" not in c])
    content = cagg["choices"][0]["logprobs"]["content"]
    assert len(content) > 0
    assert all("token" in e and e["logprob"] <= 0.0 for e in content)


def test_tp2_vocab_sharded_head_matches_tp1():
    """With vocab divisible by tp, the LM head shards over the vocab dim
    (each chip computes V/tp logit columns); results must match tp=1."""
    import jax

    mcfg = llama.preset("tiny-byte", vocab_size=260, tie_embeddings=False)
    from jax.sharding import PartitionSpec as P

    specs = llama.param_specs(mcfg, 2)
    assert specs["lm_head"] == P(None, "tp")   # actually sharded
    c1 = EngineCore(make_cfg(model=mcfg, max_batch=2), jax.devices()[:1])
    c2 = EngineCore(make_cfg(model=mcfg, max_batch=2, tp=2), jax.devices()[:2])
    c1.submit("x", req([10, 20, 30, 40], max_tokens=5))
    c2.submit("x", req([10, 20, 30, 40], max_tokens=5))
    t1 = [g.token for g in drain(c1, ["x"])["x"]]
    t2 = [g.token for g in drain(c2, ["x"])["x"]]
    assert t1 == t2


def test_moe_ep2_tp2_matches_unsharded():
    """MoE with experts over ep=2 AND expert-FFN intermediate over tp=2
    (4 devices) must reproduce the unsharded tokens exactly."""
    import jax

    mcfg = llama.preset("tiny-moe")   # intermediate 96 % 2 == 0
    c1 = EngineCore(make_cfg(model=mcfg, max_batch=2), jax.devices()[:1])
    c4 = EngineCore(make_cfg(model=mcfg, max_batch=2, ep=2, tp=2),
                    jax.devices()[:4])
    c1.submit("x", req([11, 22, 33, 44], max_tokens=5))
    c4.submit("x", req([11, 22, 33, 44], max_tokens=5))
    t1 = [g.token for g in drain(c1, ["x"])["x"]]
    t4 = [g.token for g in drain(c4, ["x"])["x"]]
    assert t1 == t4


def test_moe_sorted_dispatch_matches_dense():
    """The ragged_dot sorted dispatch must agree with the dense formulation
    (summation-order float noise only) across random shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import moe as M

    rng = jax.random.PRNGKey(0)
    for B, T, D, E, F, K in ((2, 24, 16, 4, 32, 2), (1, 64, 8, 6, 16, 3)):
        ks = jax.random.split(rng, 5)
        x = jax.random.normal(ks[0], (B, T, D), jnp.float32)
        wr = jax.random.normal(ks[1], (D, E), jnp.float32) * 0.3
        wg = jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.3
        wu = jax.random.normal(ks[3], (E, D, F), jnp.float32) * 0.3
        wd = jax.random.normal(ks[4], (E, F, D), jnp.float32) * 0.3
        vals, idx = M.route_topk(x, wr, K)
        got = M._sorted_dispatch(x, wg, wu, wd, vals, idx)
        logits = jnp.einsum("btd,de->bte", x, wr)
        probs = jax.nn.softmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(probs, K)
        vals = vals / vals.sum(-1, keepdims=True)
        gates = jnp.sum(jax.nn.one_hot(idx, E) * vals[..., None], axis=-2)
        g = jnp.einsum("btd,edf->btef", x, wg)
        u = jnp.einsum("btd,edf->btef", x, wu)
        want = jnp.einsum("btef,efd,bte->btd", jax.nn.silu(g) * u, wd, gates)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        rng = ks[0]


def test_presence_frequency_penalties_apply():
    """OpenAI penalties reach the decode sampler: a large presence penalty
    under greedy decoding makes every generated token distinct (a repeated
    token's logit drops below everything unseen), and penalties change
    outputs vs the unpenalized run (non-vacuous)."""
    core = EngineCore(make_cfg(max_batch=2))
    # prompt [6,7,8] repeats token 109 at positions 0 and 4 under plain
    # greedy decoding on the tiny model — the penalty must break that
    core.submit("plain", req([6, 7, 8], max_tokens=10))
    plain = [g.token for g in drain(core, ["plain"])["plain"]]
    assert len(set(plain)) < len(plain), "fixture lost its repeat"

    core.submit("pen", BackendInput(
        token_ids=[6, 7, 8],
        stop=StopConditions(max_tokens=10, ignore_eos=True),
        sampling=SamplingOptions(presence_penalty=100.0)))
    pen = [g.token for g in drain(core, ["pen"])["pen"]]
    assert len(pen) == len(set(pen)) == 10, pen
    assert pen != plain

    # frequency form: at counts <= 1 a -100/count bias forbids repeats the
    # same way presence does, so outputs match the presence run — while
    # actually exercising the freq_pen term (and counts resetting between
    # sequences: this run is unaffected by the previous one's history)
    core.submit("pen2", BackendInput(
        token_ids=[6, 7, 8],
        stop=StopConditions(max_tokens=10, ignore_eos=True),
        sampling=SamplingOptions(frequency_penalty=100.0)))
    pen2 = [g.token for g in drain(core, ["pen2"])["pen2"]]
    assert pen2 == pen   # deterministic + per-sequence counts


def test_penalties_zero_is_noop():
    """Default requests are bitwise unaffected by the penalty machinery."""
    core = EngineCore(make_cfg(max_batch=2))
    core.submit("a", req([9, 10, 11, 12], max_tokens=6))
    a = [g.token for g in drain(core, ["a"])["a"]]
    core.submit("b", BackendInput(
        token_ids=[9, 10, 11, 12],
        stop=StopConditions(max_tokens=6),
        sampling=SamplingOptions(frequency_penalty=0.0,
                                 presence_penalty=0.0)))
    b = [g.token for g in drain(core, ["b"])["b"]]
    assert a == b


# ----------------------------------------------------------------------
# what the engine says about itself: loop phases, dispatch counters, a
# request's stages and spans, compiles, the profiler hook. All on the
# module's ``core`` / ``engine``: nothing here compiles a bucket program.
# ----------------------------------------------------------------------
def _counter_values(counter, labels):
    return {l: counter.get(l) for l in labels}


def test_engine_phases_never_nest_and_cover_the_loop(core, monkeypatch):
    import time

    from dynamo_tpu.engine import engine as eng_mod

    import threading

    open_scopes, seen, me = [], [], threading.get_ident()

    class Scope:
        """Stands in for TraceAnnotation; only this thread's scopes count
        (the module's ``engine`` idles through its own phases beside us)."""

        def __init__(self, name):
            self.name = name

        def __enter__(self):
            if threading.get_ident() == me:
                assert not open_scopes, (self.name, "inside", open_scopes)
                open_scopes.append(self.name)
                seen.append(self.name)

        def __exit__(self, *exc):
            if threading.get_ident() == me:
                assert open_scopes.pop() == self.name

    monkeypatch.setattr(eng_mod, "_trace_annotation", Scope)
    seconds = core.stage.engine_phase_seconds
    core.phase.close()          # whatever an earlier test left open
    before = _counter_values(seconds, eng_mod.PHASES)
    t0 = time.perf_counter()
    core.submit("ph1", req(list(range(1, 71)), max_tokens=9))   # 3 chunks
    core.submit("ph2", req([3, 4, 5], max_tokens=5))
    drain(core, ["ph1", "ph2"])
    core.phase.close()
    wall = time.perf_counter() - t0
    assert not open_scopes
    # the counter is the process's: leave out the phases that only a
    # JaxEngine's own loop enters (the module's ``engine`` idles beside us)
    spent = {p: seconds.get(p) - before[p] for p in eng_mod.PHASES
             if p not in ("inbox", "deliver", "idle")}
    assert sum(spent.values()) == pytest.approx(wall, rel=0.05)
    # the core's own phases all ran; the enqueue scopes kept their names
    for p in ("admit", "prefill_build", "prefill", "prefill_fetch",
              "decode_build", "decode", "decode_fetch", "emit"):
        assert spent[p] > 0, p
    assert {n.split("[")[0] for n in seen} <= {
        "dynamo." + p for p in eng_mod.PHASES}
    assert any(n.startswith("dynamo.prefill[B") for n in seen)
    assert any(n.startswith("dynamo.decode[S") for n in seen)


def test_engine_dispatch_and_token_counters(core, monkeypatch):
    n, tokens = core.stage.engine_dispatches, core.stage.engine_dispatch_tokens
    kinds = ("prefill", "decode", "verify")
    n0, t0 = _counter_values(n, kinds), _counter_values(tokens, kinds)
    decodes = []
    run = core._run_decode_program
    monkeypatch.setattr(core, "_run_decode_program",
                        lambda *a: decodes.append(1) or run(*a))
    # a prompt no earlier test left in the prefix cache
    core.submit("cnt", req(list(range(240, 170, -1)), max_tokens=9))
    drain(core, ["cnt"])
    while core.has_work:        # the overshoot dispatch behind the finish
        core.step()
    # 70 prompt tokens in chunks of 32: three dispatches, one lane each
    assert n.get("prefill") - n0["prefill"] == 3
    assert tokens.get("prefill") - t0["prefill"] == 70
    assert n.get("decode") - n0["decode"] == len(decodes) > 0
    assert tokens.get("decode") - t0["decode"] == (
        len(decodes) * core.cfg.decode_steps)     # one active lane
    assert n.get("verify") == n0["verify"]


async def test_request_stages_add_up_to_ttft(engine):
    """Through the real HTTP frontend: every stage counts once a request,
    and the five sums are the server-side TTFT of the same requests."""
    import aiohttp

    from dynamo_tpu.llm.http_service import (HttpService, ModelManager,
                                             ServedModel)
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.pipeline import (build_chat_engine,
                                         build_completion_engine)

    card = ModelDeploymentCard(name="m")
    manager = ModelManager()
    manager.add(ServedModel(card, build_chat_engine(card, "core", engine),
                            build_completion_engine(card, "core", engine)))
    svc = HttpService(manager, host="127.0.0.1", port=0)
    port = await svc.start()
    hist = svc.stage.request_stage
    stages = ("pre_engine", "queue", "lane_wait", "prefill", "post_engine")

    def sums():
        series = hist.state()["series"]
        return ({s: series.get(s, {}).get("sum", 0.0) for s in stages},
                {s: series.get(s, {}).get("total", 0) for s in stages})

    def ttft():
        series = svc.stage.ttft.state()["series"].get("m", {})
        return series.get("sum", 0.0), series.get("total", 0)

    try:
        (sum0, cnt0), (ttft0, n0) = sums(), ttft()
        async with aiohttp.ClientSession() as s:
            for stream in (True, False):
                body = {"model": "m", "prompt": "abcdefgh", "max_tokens": 4,
                        "stream": stream}
                async with s.post(f"http://127.0.0.1:{port}/v1/completions",
                                  json=body) as r:
                    assert r.status == 200
                    await r.read()
        (sum1, cnt1), (ttft1, n1) = sums(), ttft()
        assert n1 - n0 == 2
        assert {s: cnt1[s] - cnt0[s] for s in stages} == dict.fromkeys(
            stages, 2)
        assert sum(sum1[s] - sum0[s] for s in stages) == pytest.approx(
            ttft1 - ttft0, abs=0.005)
    finally:
        await svc.stop()


async def test_engine_spans_hang_under_the_submitting_span(engine,
                                                           monkeypatch):
    from dynamo_tpu.utils import tracing

    tracer = tracing.get_tracer()
    monkeypatch.setattr(tracer, "enabled", True)
    queue_count = engine.core.stage.request_stage.get_count

    async def run_one(rid):
        with tracer.span("caller", trace_id=rid) as caller:
            async for _ in engine.generate(
                    req(list(range(1, 41)), max_tokens=5), Context(rid)):
                pass
        for _ in range(100):    # the decode span closes with the slot
            if rid not in engine.core.by_seq:
                break
            await asyncio.sleep(0.01)
        return caller

    caller = await run_one("spans-on")
    spans = {s.name: s for s in tracer.spans_for("spans-on")}
    assert {"engine.queue", "engine.prefill", "engine.decode"} <= set(spans)
    for name in ("engine.queue", "engine.prefill", "engine.decode"):
        assert spans[name].trace_id == "spans-on"
        assert spans[name].parent_id == caller.span_id
    # nothing else was in prefill: none of the wait was for a lane
    assert spans["engine.queue"].attrs == {"lane_wait_ms": 0.0}
    assert spans["engine.prefill"].attrs == {
        "prompt_tokens": 40, "prefix_hit_tokens": 0, "chunks": 2}
    assert spans["engine.decode"].attrs["output_tokens"] >= 5
    # back to back on one clock, inside the caller's span
    assert spans["engine.queue"].end == pytest.approx(
        spans["engine.prefill"].start, abs=1e-3)
    assert caller.start <= spans["engine.queue"].start + 1e-3
    assert spans["engine.prefill"].end <= caller.end + 1e-3

    # DYN_TRACING=0 (the tracer's switch): no span, the histogram counts on
    monkeypatch.setattr(tracer, "enabled", False)
    n0 = queue_count("queue")
    await run_one("spans-off")
    assert queue_count("queue") == n0 + 1
    monkeypatch.setattr(tracer, "enabled", True)
    assert tracer.spans_for("spans-off") == []


def test_lane_wait_is_the_wait_behind_another_prompt(core, monkeypatch):
    """``lane_wait`` is the part of the wait for admission during which a
    slot was free and the prefill lanes were taken: nothing for a request
    that finds a lane, nearly all of it for one behind a three-chunk
    prompt on a single lane."""
    import time

    from dynamo_tpu.engine.engine import _LaneClock
    from dynamo_tpu.utils import tracing

    clock = _LaneClock()
    t = time.monotonic()
    assert clock.read(t) == 0.0
    clock.set(True)
    clock.set(True)                 # told as often as it is evaluated
    assert clock.read(t - 1.0) == 0.0      # before the lanes filled
    assert clock.read(time.monotonic() + 2.0) >= 2.0
    clock.set(False)
    assert clock.read(time.monotonic() + 5.0) == clock.read(t) < 1.0

    tracer = tracing.get_tracer()
    monkeypatch.setattr(tracer, "enabled", True)
    monkeypatch.setattr(core, "b_buckets", [1])     # one prefill lane
    hist = core.stage.request_stage
    n0 = {s: hist.get_count(s) for s in ("queue", "lane_wait")}
    core.submit("lw1", req(list(range(200, 130, -1)), max_tokens=3))
    core.submit("lw2", req([9, 8, 7], max_tokens=3))
    drain(core, ["lw1", "lw2"])
    while core.has_work:
        core.step()
    assert {s: hist.get_count(s) - n0[s] for s in n0} == {
        "queue": 2, "lane_wait": 2}
    first, second = (
        next(s for s in tracer.spans_for(rid) if s.name == "engine.queue")
        for rid in ("lw1", "lw2"))
    assert first.attrs["lane_wait_ms"] == 0.0
    waited_ms = 1e3 * (second.end - second.start)
    # all but the iteration that admitted lw1, which saw the lane free
    assert 0.5 * waited_ms < second.attrs["lane_wait_ms"] <= waited_ms + 1e-3
    # lw2 got in only when lw1's prompt was through
    lw1_prefill = next(s for s in tracer.spans_for("lw1")
                       if s.name == "engine.prefill")
    assert second.end >= lw1_prefill.end - 1e-3


def _recording_hook(core, log):
    """A ``dispatch_hook`` that notes, for every enqueue, what was still
    unfetched at that moment."""
    def hook(kind, meta, arrays):
        log.append({"kind": kind, "chain": meta.get("chain"),
                    "last_lanes": meta.get("last_lanes"),
                    "joining": meta.get("joining"),
                    "host_tokens": "tokens" in arrays,
                    "inflight": [r["kind"] for r in core._inflight]})
    return hook


def test_streams_with_prefill_behind_the_window_equal_streams_alone(
        core, monkeypatch):
    """Multi-chunk prompts, admissions and finishes while records are in
    flight: every request's greedy stream is the one it gives alone."""
    monkeypatch.setattr(core, "b_buckets", [1])     # one prefill lane
    # a second pass must prefill again, not restore the first one's blocks
    monkeypatch.setattr(core.cfg, "enable_prefix_reuse", False)
    behind, total = (core.stage.engine_dispatches_behind,
                     core.stage.engine_dispatches)
    n0 = behind.get("prefill"), total.get("prefill")
    reqs = {
        "w0": req([7, 8, 9], max_tokens=30),
        "w1": req(list(range(150, 80, -1)), max_tokens=12),     # 3 chunks
        "w2": req(list(range(60, 100)), max_tokens=20),         # 2 chunks
        "w3": req([11, 12, 13, 14, 15], max_tokens=9),
        "w4": req(list(range(5, 105)), max_tokens=6),           # 4 chunks
        "w5": req(list(range(200, 160, -1)), max_tokens=17),
        "w6": req([21, 22], max_tokens=25),
    }
    due = {0: ["w0", "w1"], 3: ["w2", "w3", "w4"], 9: ["w5"], 14: ["w6"]}
    got = {k: [] for k in reqs}
    done = set()
    for it in range(400):
        for seq_id in due.get(it, ()):
            core.submit(seq_id, reqs[seq_id])
        for so in core.step():
            assert so.finish in (None, FinishReason.LENGTH), so
            got[so.seq_id].append(so.token)
            if so.finish is not None:
                done.add(so.seq_id)
        if done == set(reqs):
            break
    assert done == set(reqs)
    while core.has_work:
        core.step()
    n_behind = behind.get("prefill") - n0[0]
    n_total = total.get("prefill") - n0[1]
    assert n_total == sum(-(-len(r.token_ids) // 32) for r in reqs.values())
    assert n_behind >= n_total - 2          # all but the cold starts
    for seq_id, request in reqs.items():
        core.submit("alone-" + seq_id, request)
        alone = drain(core, ["alone-" + seq_id])["alone-" + seq_id]
        assert [so.token for so in alone] == got[seq_id], seq_id
    while core.has_work:
        core.step()


def test_prefill_is_enqueued_behind_the_decode_in_flight(core, monkeypatch):
    """The order of enqueues: a chunk goes behind the decode dispatch in
    flight, one chunk per decode dispatch; the decode behind a chunk chains
    on the device, and a completed prompt's first token joins it there;
    after a sequence finished with nothing to take its lane it takes host
    tokens, with no decode dispatch unfetched."""
    monkeypatch.setattr(core, "b_buckets", [1])
    monkeypatch.setattr(core.cfg, "enable_prefix_reuse", False)
    log = []
    core.submit("pb-dec", req([1, 2, 3], max_tokens=60))
    core.submit("pb-short", req([4, 5, 6], max_tokens=12))  # ends mid-prefill
    outs = []
    while not outs:
        outs = core.step()
    core.step()
    monkeypatch.setattr(core, "dispatch_hook", _recording_hook(core, log))
    core.submit("pb-long", req(list(range(100)), max_tokens=4))  # 4 chunks
    done = False
    while not done:
        done = any(so.seq_id == "pb-long" and so.finish is not None
                   for so in core.step())
    core.cancel("pb-dec")
    while core.has_work:
        core.step()
    chunks = [i for i, e in enumerate(log) if e["kind"] == "prefill"]
    assert [bool(log[i]["last_lanes"]) for i in chunks] == [
        False, False, False, True]
    chained = 0
    for i in chunks:
        assert "decode" in log[i]["inflight"], log[i]
        after = log[i + 1]
        assert after["kind"] == "decode"    # one chunk per decode dispatch
        if after["chain"]:
            chained += 1
            assert not after["host_tokens"]
            assert after["inflight"][-1] == "prefill"
            # pb-long's last chunk holds its first token: slot 2 takes it
            # from the chunk's lane 0 on the device
            assert after["joining"] == (
                [(2, 0)] if log[i]["last_lanes"] else [])
        else:
            # pb-short has finished and only the chunk is still unfetched
            assert after["host_tokens"] and not log[i]["last_lanes"]
            assert after["inflight"] == ["prefill"]
    assert chained == 3


def test_a_first_token_joins_the_chained_decode_on_the_device(core,
                                                              monkeypatch):
    """Every lane taken and more requests waiting (a closed loop): a lane
    that finishes is refilled, and the chunk that completes the new prompt
    hands its first token to the chained dispatch on the device, in the
    finished lane's place. While requests wait no dispatch after the first
    takes host tokens, and every stream, penalised ones included (the
    joining lane's counts restart), is the one the request gives alone."""
    monkeypatch.setattr(core.cfg, "enable_prefix_reuse", False)
    log = []
    monkeypatch.setattr(core, "dispatch_hook", _recording_hook(core, log))
    pen = SamplingOptions(frequency_penalty=0.7, presence_penalty=0.4)
    reqs = {f"j{k}": req([30 + k, 31, 32 + k], max_tokens=10 + 7 * (k % 4),
                         **({"sampling": pen} if k % 3 == 0 else {}))
            for k in range(10)}
    # a request that ends with its first token, already joined when it does
    reqs["j5"].stop.max_tokens = 1
    for seq_id, request in reqs.items():
        core.submit(seq_id, request)
    got = drain(core, list(reqs))
    while core.has_work:
        core.step()
    monkeypatch.setattr(core, "dispatch_hook", None)
    decodes = [e for e in log if e["kind"] == "decode"]
    joined = [n for n, e in enumerate(decodes) if e["joining"]]
    # four lanes: the first four prompts start cold, the other six each take
    # a finished lane's place, every lane at least once
    assert sum(len(decodes[n]["joining"]) for n in joined) == len(reqs) - 4
    assert {i for n in joined for i, _ in decodes[n]["joining"]} == \
        {0, 1, 2, 3}
    # while requests wait, only the first dispatch takes host tokens
    assert [e["host_tokens"] for e in decodes[:joined[-1] + 1]] == \
        [True] + [False] * joined[-1]
    for seq_id, request in reqs.items():
        core.submit("alone-" + seq_id, request)
        alone = drain(core, ["alone-" + seq_id])["alone-" + seq_id]
        assert [so.token for so in alone] == \
            [so.token for so in got[seq_id]], seq_id
        assert alone[-1].logprob == pytest.approx(got[seq_id][-1].logprob,
                                                  abs=1e-3)
    while core.has_work:
        core.step()
    assert core.active == 0 and not core._deferred_release
    assert core.pool.free_pages == core.pool.num_pages - 1


def test_freed_pages_come_back_when_their_records_are_fetched(
        core, monkeypatch):
    """A sequence freed with records in flight keeps its pages until
    exactly those records are fetched, not until the window is empty; and
    three waves of full-context requests pass through a pool sized for one
    without an error or an admission left waiting for held pages."""
    monkeypatch.setattr(core, "b_buckets", [1])
    monkeypatch.setattr(core.cfg, "enable_prefix_reuse", False)
    held, released = {}, []
    free_slot, release, admit = (core._free_slot, core.pool.release,
                                 core._admit_one)

    def spy_free(i):
        slot = core.slots[i]
        if slot is not None and core._inflight:
            held[slot.seq_id] = {r["seq"] for r in core._inflight}
        free_slot(i)

    def spy_release(seq_id):
        if seq_id in held:
            now = {r["seq"] for r in core._inflight}
            assert not (held.pop(seq_id) & now), seq_id
            released.append(len(now))
        release(seq_id)

    def spy_admit(out):
        res = admit(out)
        # never left waiting for pages that a deferred release holds
        assert res != "blocked" or not core._deferred_release
        return res

    monkeypatch.setattr(core, "_free_slot", spy_free)
    monkeypatch.setattr(core.pool, "release", spy_release)
    monkeypatch.setattr(core, "_admit_one", spy_admit)
    n = 3 * core.cfg.max_batch
    # 60 + 52..63 tokens of a 128-token context: a lane's share of the pool
    reqs = {f"pg{j}": req([(j + 3 * t) % 250 for t in range(60)],
                          max_tokens=52 + j) for j in range(n)}
    for seq_id, request in reqs.items():
        core.submit(seq_id, request)
    got = drain(core, list(reqs))
    while core.has_work:
        core.step()
    for seq_id, request in reqs.items():
        assert got[seq_id][-1].finish == FinishReason.LENGTH
        assert len(got[seq_id]) == request.stop.max_tokens
        assert all(so.error is None for so in got[seq_id])
    assert not held and not core._deferred_release
    assert len(released) >= n // 2      # most finish behind a chained decode
    assert any(released)                # ... and some with the window busy


# ---- what leaves the engine thread: an iteration in ONE cross-thread call,
# and a lane's tokens of one dispatch as ONE output all the way out --------

@pytest.fixture(scope="module")
def burst_engine():
    eng = JaxEngine(make_cfg(max_batch=4, decode_steps=4))
    yield eng
    eng.shutdown()


class _StubLoop:
    """Stands where the event loop stands: counts the cross-thread calls and
    runs each where it is made."""

    def __init__(self):
        self.calls = []

    def call_soon_threadsafe(self, fn, *args):
        self.calls.append(args)
        fn(*args)


class _TokenAtATime:
    """The parent's path: every ``StepOutput`` its own ``EngineOutput``."""

    def __init__(self, step_outputs):
        self.step_outputs = step_outputs

    async def generate(self, request, context):
        for so in self.step_outputs:
            yield EngineOutput(token_ids=[so.token], cum_log_prob=so.logprob,
                               logprobs=[{str(so.token): so.token_logprob}],
                               finish_reason=so.finish)


def _token_logprobs(outs):
    return [lp for o in outs for m in o.logprobs for lp in m.values()]


def test_an_iteration_crosses_to_the_loop_in_one_call(burst_engine,
                                                       monkeypatch):
    eng, stub, iterations = burst_engine, _StubLoop(), []
    real_step = eng.core.step

    def step():
        outs = real_step()
        if outs:
            iterations.append(outs)
        return outs

    monkeypatch.setattr(eng.core, "step", step)
    monkeypatch.setattr(eng, "_loop", stub)
    stage = eng.core.stage
    n0, t0 = stage.engine_handoffs.get(), stage.engine_handoff_tokens.get()
    seqs = {f"h{i}": req([40 + i, 41, 42], max_tokens=17) for i in range(3)}
    queues = {s: asyncio.Queue() for s in seqs}
    eng._queues.update(queues)
    try:
        for seq_id, request in seqs.items():
            eng._inbox.put(("submit", seq_id, (request,)))
        eng._wake.set()
        deadline = time.monotonic() + 120
        while (sum(b[-1].finish is not None
                   for puts, in stub.calls for _, b in puts) < len(seqs)
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        for s in seqs:
            eng._queues.pop(s, None)
    calls = [puts for puts, in stub.calls]
    # one call an iteration, whatever the lanes and steps ...
    assert len(calls) == len(iterations)
    assert [sum(len(b) for _, b in puts) for puts in calls] == \
        [len(outs) for outs in iterations]
    # ... in which a sequence is ONE queue item, holding what the dispatch
    # gave it: three lanes x decode_steps in the fullest
    for puts in calls:
        assert len({id(q) for q, _ in puts}) == len(puts)
        assert all(len({so.seq_id for so in b}) == 1 for _, b in puts)
    fullest = max(calls, key=lambda puts: sum(len(b) for _, b in puts))
    assert sorted(len(b) for _, b in fullest) == [4, 4, 4]
    for seq_id, q in queues.items():
        bursts = [q.get_nowait() for _ in range(q.qsize())]
        assert [so.seq_id for b in bursts for so in b] == [seq_id] * 17
        assert len(bursts[0]) == 1 and bursts[0][0].first_token_at
        assert bursts[-1][-1].finish == FinishReason.LENGTH
    assert stage.engine_handoffs.get() - n0 == len(calls)
    assert stage.engine_handoff_tokens.get() - t0 == 3 * 17


async def test_a_lanes_tokens_of_one_dispatch_are_one_output(burst_engine):
    reqs = [req([50 + i, 51, 52, 53], max_tokens=11) for i in range(3)]

    async def one(r):
        return [o async for o in burst_engine.generate(r, Context())]

    for outs in await asyncio.gather(*map(one, reqs)):
        sizes = [len(o.token_ids) for o in outs]
        assert sum(sizes) == 11 and sizes[0] == 1
        assert max(sizes) == 4          # decode_steps tokens in one output
        assert len(outs) <= 1 + 3       # not an output a token
        running = 0.0
        for o in outs:
            # one logprob entry a token, keyed by the token; the cumulative
            # one is the last token's
            assert [list(m) for m in o.logprobs] == \
                [[str(t)] for t in o.token_ids]
            running += sum(lp for m in o.logprobs for lp in m.values())
            assert o.cum_log_prob == pytest.approx(running, abs=1e-4)
        assert [o.finish_reason for o in outs] == \
            [None] * (len(outs) - 1) + [FinishReason.LENGTH]
        assert outs[0].kv_prefix_hit_tokens is not None
        assert all(o.kv_prefix_hit_tokens is None for o in outs[1:])


async def test_bursts_stream_what_a_token_at_a_time_streamed(burst_engine,
                                                             core):
    """Greedy, fixed weights: ids, text and per-token logprobs of the burst
    path equal those of one output a token (what ``_consume`` yielded before
    a burst was the unit), through the same detokeniser."""
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    prompt = tok.encode("the quick brown fox")
    core.submit("ref", req(prompt, max_tokens=23))
    ref = drain(core, ["ref"])["ref"]
    want = [o async for o in Backend(_TokenAtATime(ref), tok).generate(
        req(prompt, max_tokens=23), Context())]
    got = [o async for o in Backend(burst_engine, tok).generate(
        req(prompt, max_tokens=23), Context())]
    assert len(got) < len(want) == 23
    assert [t for o in got for t in o.token_ids] == \
        [t for o in want for t in o.token_ids] == [so.token for so in ref]
    assert "".join(o.text for o in got) == "".join(o.text for o in want)
    assert _token_logprobs(got) == pytest.approx(_token_logprobs(want),
                                                 abs=1e-4)
    assert got[-1].cum_log_prob == pytest.approx(want[-1].cum_log_prob,
                                                 abs=1e-4)
    assert got[-1].finish_reason == want[-1].finish_reason


async def test_a_finish_inside_a_burst_ends_the_burst(burst_engine):
    # 1 first token + a dispatch of 4 + the first step of the next dispatch
    outs = [o async for o in burst_engine.generate(
        req([60, 61, 62], max_tokens=6), Context())]
    assert [len(o.token_ids) for o in outs] == [1, 4, 1]
    assert [o.finish_reason for o in outs] == \
        [None, None, FinishReason.LENGTH]
    # an EOS at a dispatch's second step: nothing of that dispatch after it
    ids = [t for o in outs for t in o.token_ids]
    assert ids[2] not in ids[:2]
    eos = BackendInput(token_ids=[60, 61, 62],
                       stop=StopConditions(max_tokens=10),
                       eos_token_ids=[ids[2]])
    outs = [o async for o in burst_engine.generate(eos, Context())]
    assert [o.token_ids for o in outs] == [ids[:1], ids[1:3]]
    assert len(outs[-1].logprobs) == 2
    assert outs[-1].finish_reason == FinishReason.EOS
    while burst_engine.core.active:
        await asyncio.sleep(0.01)


async def test_a_stop_string_inside_a_burst_cuts_the_burst():
    """The client is given the text up to the stop, so ids, logprobs and
    usage end at the token that completed it, not at the burst's end."""
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.pipeline import build_completion_engine
    from dynamo_tpu.llm.protocols.openai import CompletionRequest

    class Bursts:
        async def generate(self, request, context):
            for burst in (b"a", b"bcde", b"f;gh", b"ijkl"):
                yield EngineOutput(
                    token_ids=list(burst), cum_log_prob=-0.5 * len(burst),
                    logprobs=[{str(t): -0.5} for t in burst])

    comp = build_completion_engine(ModelDeploymentCard(name="m"), "core",
                                   Bursts())
    chunks = [c async for c in comp.generate(CompletionRequest.from_dict({
        "model": "m", "prompt": "x", "max_tokens": 32, "stop": [";"],
        "logprobs": 1}), Context()) if "event" not in c]
    choices = [c["choices"][0] for c in chunks]
    assert "".join(c["text"] for c in choices) == "abcdef"
    assert choices[-1]["finish_reason"] == "stop"
    lps = [lp for c in choices if c.get("logprobs")
           for lp in c["logprobs"]["token_logprobs"]]
    assert len(lps) == 7                    # a … f and the stop's own token
    assert chunks[-1]["usage"]["completion_tokens"] == 7


def test_an_error_crosses_alone(burst_engine, monkeypatch):
    from dynamo_tpu.engine.engine import StepOutput

    eng, stub = burst_engine, _StubLoop()
    monkeypatch.setattr(eng, "_loop", stub)
    qa, qb = asyncio.Queue(), asyncio.Queue()
    monkeypatch.setitem(eng._queues, "ea", qa)
    monkeypatch.setitem(eng._queues, "eb", qb)
    err = StepOutput("ea", 0, 0.0, FinishReason.ERROR, error="boom",
                     error_code=400, error_stage="engine")
    eng._hand_off([StepOutput("ea", 1, -0.1), StepOutput("ea", 2, -0.2),
                   StepOutput("eb", 3, -0.1), err,
                   StepOutput("gone", 9, -0.1), StepOutput("eb", 4, -0.2)])
    assert len(stub.calls) == 1
    assert [[so.token for so in qa.get_nowait()] for _ in range(2)] == \
        [[1, 2], [0]]
    assert [so.token for so in qb.get_nowait()] == [3, 4] and qb.empty()
    eng._hand_off([StepOutput("gone", 9, -0.1)])    # nobody listens: no call
    assert len(stub.calls) == 1


async def test_an_engine_error_is_an_output_of_its_own(burst_engine):
    # alongside a stream in flight: the refusal neither joins nor cuts it
    async def one(r):
        return [o async for o in burst_engine.generate(r, Context())]

    ok, bad = await asyncio.gather(
        one(req([70, 71, 72], max_tokens=9)),
        one(req(list(range(200)), max_tokens=4)))      # > max_context 128
    assert [o.finish_reason for o in bad] == [FinishReason.ERROR]
    assert bad[0].token_ids == [] and bad[0].error
    assert sum(len(o.token_ids) for o in ok) == 9
    assert ok[-1].finish_reason == FinishReason.LENGTH


async def test_cancel_between_two_bursts_frees_the_slot(burst_engine):
    core = burst_engine.core
    while core.has_work:
        await asyncio.sleep(0.01)
    free = core.pool.free_pages
    ctx, outs = Context(), []
    async for o in burst_engine.generate(req([80, 81, 82], max_tokens=100),
                                         ctx):
        outs.append(o)
        if len(outs) == 3:          # first token and two whole bursts
            ctx.stop_generating()
            break
    assert [len(o.token_ids) for o in outs] == [1, 4, 4]
    for _ in range(500):
        if not core.has_work and core.pool.free_pages == free:
            break
        await asyncio.sleep(0.01)
    assert core.active == 0 and not core.by_seq
    assert core.pool.free_pages == free
    assert ctx.id not in burst_engine._queues


def test_xla_compile_listener_counts_a_program_once(core):
    import jax
    import jax.numpy as jnp

    compiles = core.stage.xla_compiles     # EngineCore registered it
    seconds = core.stage.xla_compile_seconds
    x = jnp.arange(7.0)
    fresh = jax.jit(lambda v: v * 3.0 + 11.0)   # no other test builds this
    n0, s0 = compiles.get(), seconds.get()
    fresh(x).block_until_ready()
    assert compiles.get() == n0 + 1
    assert seconds.get() > s0
    fresh(x).block_until_ready()
    assert compiles.get() == n0 + 1


def test_profile_capture_python_tracer_off_and_stop_off_thread(
        monkeypatch, tmp_path):
    import threading

    import jax

    from dynamo_tpu.engine.engine import _ProfileCapture

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, **kw: calls.append(("start", d, kw["profiler_options"])))
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: calls.append(("stop", threading.current_thread().name)))
    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("DYN_PROFILE_STEPS", "2")
    capture = _ProfileCapture()

    def engine_thread():
        for _ in range(3):          # the third iteration is past the capture
            capture.before_step()
            capture.after_step()

    t = threading.Thread(target=engine_thread, name="jax-engine")
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    capture.close(timeout=10)
    assert [c[0] for c in calls] == ["start", "stop"]
    assert calls[0][1] == str(tmp_path)
    assert calls[0][2].python_tracer_level == 0
    assert calls[1][1] != "jax-engine"

    # cut short by shutdown: close() still stops and waits for the writer
    monkeypatch.setenv("DYN_PROFILE_STEPS", "50")
    short = _ProfileCapture()
    short.before_step()
    short.after_step()
    short.close(timeout=10)
    assert [c[0] for c in calls[2:]] == ["start", "stop"]

    # the engine thread's last stop() and shutdown's close() at once: one
    # of them ends the capture, the other finds it ended
    raced = _ProfileCapture()
    raced.before_step()
    both = [threading.Thread(target=raced.stop) for _ in range(2)]
    for t in both:
        t.start()
    for t in both:
        t.join(timeout=10)
    raced.close(timeout=10)
    assert [c[0] for c in calls[4:]] == ["start", "stop"]


# ---------------------------------------------------------------------------
# the pools are stored once and updated in place (PR 26)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec_core():
    return EngineCore(make_cfg(max_batch=2, spec="ngram", spec_k=2))


def _bucket_program(core, kind):
    """(jitted bucket program, its arguments) for the first bucket of
    ``kind``, with the arguments warm-up gives it."""
    import jax.numpy as jnp

    B, s, S = core.cfg.max_batch, core.sampling, core.s_buckets[0]
    pt = np.zeros((B, S // core.page_size), np.int32)
    ones, flags = np.ones(B, np.int32), np.zeros(B, bool)
    tail = (pt, ones, s.temperature, s.top_p, s.top_k, s.key,
            core.gen_counts, flags, flags, s.freq_pen, s.pres_pen)
    if kind == "decode":
        return core._decode_fn(S), (
            core.params, np.zeros(B, np.int32), core.k_pool, core.v_pool,
            *tail)
    if kind == "verify":
        K, U = core.spec.k_buckets[0], core.spec.k_max + 1
        return core._verify_fn(S, K), (
            core.params, np.zeros((B, K + 1), np.int32), core.k_pool,
            core.v_pool, *tail, np.zeros((B, U), np.int32),
            np.zeros((B, U), bool))
    C = core.c_buckets[0]
    zt = np.zeros((1, C), np.int32)
    zs = np.zeros((1, S), np.int32)
    return core._prefill_fn(1, C, S), (
        core.params, zt, zt, core.k_pool, core.v_pool, zt, zs, zs,
        np.zeros((1, S), bool), np.zeros(1, np.int32),
        np.zeros(1, np.float32), np.ones(1, np.float32),
        np.zeros(1, np.int32), s.key[jnp.asarray(np.zeros(1, np.int32))])


@pytest.mark.parametrize("kind", ["decode", "prefill", "verify"])
def test_bucket_programs_alias_both_pools(core, spec_core, kind):
    """Every bucket program takes the pools donated and returns them
    aliased to their inputs: the compiled program's ``input_output_alias``
    names both pool parameters, and one call deletes the arrays passed in
    and hands back pools that carry ``kv_sharding``."""
    import re

    c = spec_core if kind == "verify" else core
    fn, args = _bucket_program(c, kind)
    text = fn.jitted.lower(*args).compile().as_text()
    aliased = {int(n) for n in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)",
        re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1))}
    shape = ",".join(map(str, c.k_pool.shape))
    pool_params = {int(n) for n in re.findall(
        r"\w+\[%s\]\S* parameter\((\d+)\)" % shape,
        text[text.index("ENTRY"):])}
    assert len(pool_params) == 2 and pool_params <= aliased

    k0, v0 = c.k_pool, c.v_pool
    out = fn(*args)
    pools = [a for a in out if getattr(a, "shape", None) == k0.shape]
    assert k0.is_deleted() and v0.is_deleted() and len(pools) == 2
    assert all(p.sharding == c.kv_sharding for p in pools)
    c.k_pool, c.v_pool = pools
    if kind != "prefill":
        c.gen_counts = out[-1]


def test_serving_dispatches_donate_the_pools(core, spec_core):
    """The same, seen from the serving loop: whenever a step replaces the
    engine's pools (a prefill, decode or verify dispatch went out), the
    arrays it replaced are gone."""
    for c, kinds in ((core, ("prefill", "decode")),
                     (spec_core, ("prefill", "verify"))):
        n0 = _counter_values(c.stage.engine_dispatches, kinds)
        c.submit("don", req(list(range(100, 140)), max_tokens=7))
        done, swaps = False, 0
        for _ in range(200):
            k0, v0 = c.k_pool, c.v_pool
            done |= any(so.finish is not None for so in c.step())
            if c.k_pool is not k0:
                swaps += 1
                assert k0.is_deleted() and v0.is_deleted()
                assert c.k_pool.sharding == c.kv_sharding
                assert c.v_pool.sharding == c.kv_sharding
            if done and not c.has_work:
                break
        n1 = _counter_values(c.stage.engine_dispatches, kinds)
        assert all(n1[k] > n0[k] for k in kinds) and swaps >= 3


def _hf(module):
    """The tiny published-style configuration a model's own test file
    serves against its reference."""
    import importlib

    import jax.numpy as jnp

    tiny = importlib.import_module(f"tests.{module}").TINY
    return llama.LlamaConfig.from_hf_config(tiny, dtype=jnp.float32)


def _preset(name, **over):
    import jax.numpy as jnp

    return llama.preset(name, dtype=jnp.float32, **over)


# one case a cache kind: model, page size, what writes each kind's decode rows
CACHE_KINDS = {
    "rows-128": (lambda: _preset("tiny-qwen", head_dim=128), 8, "kernel"),
    "rows-64-folded": (lambda: _preset("tiny-byte", head_dim=64, kv_fold=2),
                       8, "kernel"),
    "rows-16-folded": (lambda: _preset("tiny-byte", kv_fold=8), 8, "kernel"),
    "narrow-unfolded": (lambda: _preset("tiny-byte"), 8, "scatter"),
    "window-softcap": (lambda: _preset("tiny-gemma2", head_dim=128), 8,
                       "kernel"),
    "mimo-window-sink": (lambda: _hf("test_mimo_v2_flash"), 8, "kernel"),
    "keye-selection": (lambda: _preset("tiny-keye"), 8, "kernel"),
    "deepseek-latent": (lambda: _hf("test_deepseek_v2"), 8, "kernel"),
    "granite-state": (lambda: _hf("test_granite_hybrid"), 16, "kernel"),
    "lfm2-conv-tail": (lambda: _hf("test_lfm2_moe"), 16, "kernel"),
}


@pytest.mark.parametrize("kind", list(CACHE_KINDS))
def test_pallas_decode_path_serves_what_the_xla_path_serves(kind):
    """A seeded greedy run through ``EngineCore`` on the decode program the
    chip runs (the paged kernel in the interpreter, reading the whole pool
    by layer index and writing the step's rows wherever the pool is stored
    as it reads it) gives the tokens of the same run on the dense gather
    path, computed here, in this process, and log-probabilities within
    float32 round-off. The models are float32 so that the comparison sees
    the paths and not bf16 ties."""
    build, page, writes = CACHE_KINDS[kind]
    model = build()
    runs = {}
    for impl in ("xla", "pallas"):
        c = EngineCore(make_cfg(model=model, max_batch=2, attn_impl=impl,
                                seed=3, page_size=page, max_context=96,
                                prefill_chunk=16, decode_steps=2))
        assert c.decode_attn_impl == impl
        if impl == "pallas":
            assert c.paged_kernel == "dma[interpret]"
            assert c.decode_kv_write == writes
        c.submit("p", req([7, 3, 9, 250, 14, 15, 92, 65, 35], max_tokens=12))
        c.submit("q", req(list(range(40, 85)), max_tokens=12))
        runs[impl] = drain(c, ["p", "q"])
    for seq in ("p", "q"):
        x, p = runs["xla"][seq], runs["pallas"][seq]
        assert [g.token for g in x] == [g.token for g in p]
        np.testing.assert_allclose([g.token_logprob for g in p],
                                   [g.token_logprob for g in x], atol=2e-5)


def test_the_page_counters_count_on_a_cpu_engine():
    """What ``attn.live_page_share`` reads on the chip moves under the CPU
    tests too: one request through a pallas engine, and
    ``dyn_attn_pages_live_total`` has counted its decode dispatches."""
    c = EngineCore(make_cfg(attn_impl="pallas", max_batch=2))
    live0 = c.stage.attn_pages_live.get("full")
    c.submit("n", req([5, 6, 7, 8], max_tokens=4))
    drain(c, ["n"])
    assert c.stage.attn_pages_live.get("full") > live0


def test_a_decode_dispatch_counts_the_pages_its_kernel_copies():
    """tiny-gemma2 (a full layer and a window layer, window 8 = one page)
    on four lanes through the paged kernel (interpreter): the tokens are the
    dense path's (float32, as above), and every decode dispatch moves
    ``dyn_attn_pages_live_total`` / ``dyn_attn_pages_visited_total`` by what
    ``paged_live_pages`` gives for the lanes of the program as the KERNEL is
    handed them (the two the dispatch does not serve: length 0, skipped, no
    page), a token longer each step, by attention kind;
    ``dyn_attn_lane_calls_total`` by lanes x steps x layers and
    ``dyn_attn_lane_calls_skipped_total`` by the unserved part of them; a
    capture counts its own dispatches a second time, under the counters'
    names."""
    from dynamo_tpu.ops import attention as A

    import jax.numpy as jnp

    cfg = dict(model=llama.preset("tiny-gemma2", dtype=jnp.float32),
               max_batch=4)
    reqs = {"a": req(list(range(3, 40)), max_tokens=9),
            "b": req([7, 8, 9], max_tokens=6)}

    def serve(core):
        for name, r in reqs.items():
            core.submit(name, r)
        got = drain(core, list(reqs))
        while core.has_work:        # the overshoot dispatch behind the finish
            core.step()
        return ({n: [g.token for g in got[n]] for n in reqs},
                np.asarray([g.token_logprob for n in reqs for g in got[n]]))

    dense = EngineCore(make_cfg(attn_impl="xla", **cfg))
    st = dense.stage
    counters = {"live": st.attn_pages_live, "visited": st.attn_pages_visited,
                "lanes": st.attn_lane_calls,
                "skipped": st.attn_lane_calls_skipped}

    def counted():
        # (the series are the process's: what earlier engines of this worker
        # left there is the baseline)
        return {(name, kind): c.get(kind) for name, c in counters.items()
                for kind in ("full", "window")}

    base = counted()
    want, want_logps = serve(dense)
    assert not dense._attn_calls and counted() == base  # no kernel, no pages
    # a model without a window has one kind: every layer's call is ``full``
    assert EngineCore(make_cfg(attn_impl="pallas", max_batch=2))._attn_calls \
        == {"full": (None, llama.preset("tiny-byte").num_layers)}
    core = EngineCore(make_cfg(attn_impl="pallas", **cfg))
    assert core.paged_kernel == "dma[interpret]"
    assert core._attn_calls == {"full": (None, 1), "window": (8, 1)}
    seen = []
    core.dispatch_hook = lambda kind, meta, arrs: kind == "decode" and (
        seen.append((meta["S"], arrs["lengths"].copy(),
                     arrs["active_mask"].copy(), core.capturing)))
    captured0 = dict(st.profile_captured_work._values)
    got, logps = serve(core)
    assert got == want
    np.testing.assert_allclose(logps, want_logps, atol=2e-5)
    assert st.profile_captured_work._values == captured0
    core.capturing = True
    try:
        reqs = {"c": req(list(range(5, 30)), max_tokens=5)}
        serve(core)
    finally:
        core.capturing = False

    N, page = core.cfg.decode_steps, core.page_size
    want = {}
    for S, lengths, served, captured in seen:
        # the lanes not served: a slot for their row, nothing to attend over
        assert (~served).sum() >= 2 and (lengths[~served] == 1).all()
        at = np.where(served[:, None], lengths[:, None] + np.arange(N), 0)
        for kind, window in (("full", None), ("window", 8)):
            pages = A.paged_live_pages(at, S // page, page, 8, window)
            assert not pages[0][~served].any() and pages[0][served].all()
            for name, n in zip(
                    ("live", "visited", "lanes", "skipped"),
                    (*pages, np.int64(4 * N), (~served).sum() * N)):
                for key in [(name, kind)] + [(name, kind, "cap")] * captured:
                    want[key] = want.get(key, 0) + n.sum()    # one layer each
    assert any(c for *_, c in seen) and not all(c for *_, c in seen)
    moved = {key: n - base[key] for key, n in counted().items()}
    for name, counter in counters.items():
        for kind in ("full", "window"):
            assert moved[name, kind] == want[name, kind] > 0
            assert (st.profile_captured_work.get(counter.name, kind)
                    - captured0.get((counter.name, kind), 0)
                    ) == want[name, kind, "cap"] > 0
    # a window of one page sees two pages at most; a block holds eight
    assert moved["live", "window"] < moved["live", "full"]
    assert moved["live", "full"] < moved["visited", "full"]
    # half the program's lanes or more were never served
    assert (moved["lanes", "full"] / 2 <= moved["skipped", "full"]
            < moved["lanes", "full"])
