"""The tree an engine hands its bucket programs (``llama.stored_params``:
``wq`` / ``wk`` / ``wv`` a matrix [H x Dh, D] a layer, as the matmuls read
them) against the published one (``llama.init_params``: a stack [L, D, H,
Dh]): the same values, the same outputs of ``forward`` and
``forward_decode`` (``layer_in`` reads either, by the weight's rank), the
same checkpoint from the exporter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.cache import WindowPages, cache_kinds
from dynamo_tpu.models import llama

PAGE, N_PAGES = 8, 7
TABLES = [[2, 5, 1], [4, 3, 6]]          # pages, per lane


def _mimo():
    """mimo's tiny configuration at the published head widths: keys 192
    wide (stored 256), values 128, window layers with heads of their own."""
    from test_mimo_v2_flash import TINY

    return llama.LlamaConfig.from_hf_config(dict(
        TINY, head_dim=192, v_head_dim=128, swa_head_dim=192,
        swa_v_head_dim=128), dtype=jnp.float32)


CASES = {
    "qwen-bias": lambda: llama.preset("tiny-qwen", dtype=jnp.float32),
    "gemma3-qk-norm": lambda: llama.preset("tiny-gemma3", dtype=jnp.float32),
    "mimo-keys-192": _mimo,
    "tp2": lambda: llama.preset("tiny-byte", dtype=jnp.float32),
}


def _trees(case):
    """-> (cfg, mesh, published tree, stored tree), both placed: on the
    two-device tensor-parallel mesh of the ``tp2`` case, each by its own
    specs (the stored tree's merged axis carries the heads' sharding)."""
    cfg = CASES[case]()
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    if case != "tp2":
        return cfg, None, params, llama.stored_params(params)
    from dynamo_tpu.parallel.mesh import tp_mesh

    mesh = tp_mesh(2)
    assert llama.param_specs(cfg, 2, stored=True)["layers"]["wq"] == (
        P("tp", None),) * cfg.num_layers

    def placed(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), tree,
            specs)
    published = placed(params, llama.param_specs(cfg, 2))
    by_specs = placed(llama.stored_params(params),
                      llama.param_specs(cfg, 2, stored=True))
    # ... and what the engine does: the placed tree, cut on the devices,
    # each matrix as sharded as its stack was
    stored = llama.stored_params(published)
    for a, b in zip(jax.tree.leaves(stored), jax.tree.leaves(by_specs)):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
    return cfg, mesh, published, stored


def _operands(cfg, T0=12, steps=3):
    """Pools of every cache kind (random, so that a decode step reads a
    context), a prompt of two lanes and what addresses it."""
    kinds = cache_kinds(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 4))
    pools = [0.3 * jax.random.normal(next(keys), shape, jnp.float32)
             for k in kinds for shape in k.pool_shapes(N_PAGES, PAGE)]
    pt = jnp.asarray(TABLES, jnp.int32)
    t = jnp.arange(pt.shape[1] * PAGE, dtype=jnp.int32)
    slots = pt[:, t // PAGE] * PAGE + t % PAGE
    rpos = jnp.broadcast_to(t, slots.shape)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        1, 250, (2, T0 + steps)), jnp.int32)
    return pools, pt, slots, rpos, tokens


def _forward(cfg, mesh, T0=12):
    pools, pt, slots, rpos, tokens = _operands(cfg, T0)
    win = {}
    if len(pools) == 4:
        # a fresh prompt: nothing of it in the window cache yet
        Sw = WindowPages.chunk_read_pages(
            cache_kinds(cfg)[1].window, T0, PAGE) * PAGE
        win = {"win": (*pools[2:], slots[:, :T0],
                       jnp.zeros((2, Sw // PAGE), jnp.int32),
                       jnp.zeros((2, Sw), jnp.int32),
                       jnp.zeros((2, Sw), bool)), "read_pages": pt}
    fn = jax.jit(lambda p: llama.forward(
        p, cfg, tokens[:, :T0], rpos[:, :T0], *pools[:2], slots[:, :T0],
        slots, rpos, rpos < T0, mesh=mesh, **win))
    return lambda p: [np.asarray(a) for a in fn(p)]


def _forward_decode(cfg, mesh, T0=12, steps=3):
    pools, pt, _, _, tokens = _operands(cfg, T0, steps)

    def step(p, tok, ln, k, v, *w):
        kw = {"win": (*w, pt)} if w else {}
        return llama.forward_decode(p, cfg, tok, k, v, pt, ln, mesh=mesh,
                                    **kw)
    fn = jax.jit(step)

    def run(p):
        state, out = list(pools), []
        for n in range(T0, T0 + steps):
            lg, *state = fn(p, tokens[:, n], jnp.full((2,), n + 1, jnp.int32),
                            *state)
            out.append(np.asarray(lg))
        return out + [np.asarray(a) for a in state]
    return run


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("program", [_forward, _forward_decode],
                         ids=["forward", "forward_decode"])
def test_the_stored_tree_serves_what_the_published_tree_serves(case, program):
    """Logits and every pool written, at the tolerance of
    ``test_llama_model.py``'s ``test_forwards_agree`` (float32 weights; the
    three matmuls accumulate in another order, so not bit for bit): a model
    with q / k / v biases, one with q / k norms, a per-kind model whose keys
    are 192 wide and whose window layers have heads of their own, and a
    tensor-parallel mesh of two devices (a shard of the merged axis is whole
    heads)."""
    cfg, mesh, published, stored = _trees(case)
    run = program(cfg, mesh)
    for got, want in zip(run(stored), run(published)):
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("case", ["qwen-bias", "mimo-keys-192"])
def test_stored_params_is_a_reshape_and_a_transpose(case):
    """Stack by stack: ``wq`` / ``wk`` / ``wv`` of every attention stack are
    the published values, a matrix [H x width, D] a layer; every other leaf
    is the very array it was; host arrays come back as views; a stored tree
    comes back as it is; ``donate`` deletes the stacks it cut and nothing
    else; and ``init_params`` still returns the published shapes."""
    cfg = CASES[case]()
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    stored = llama.stored_params(params)
    host = llama.stored_params(jax.tree.map(np.asarray, params))

    def stacks(tree):
        return (tree[llama.STACKS] if llama.STACKS in tree
                else {"layers": tree["layers"]})
    seen = 0
    for kind, st in stacks(params).items():
        for name, a in st.items():
            b, h = stacks(stored)[kind][name], stacks(host)[kind][name]
            if name not in llama.ATTN_IN:
                assert b is a
                continue
            seen += 1
            n, D, H, width = a.shape
            assert D == cfg.hidden_size and len(b) == len(h) == n
            want = np.asarray(a).reshape(n, D, H * width).transpose(0, 2, 1)
            for l in range(n):
                assert b[l].shape == (H * width, D)
                np.testing.assert_array_equal(np.asarray(b[l]), want[l])
                np.testing.assert_array_equal(h[l], want[l])
                assert isinstance(h[l], np.ndarray) and h[l].base is not None
    assert seen == (6 if cfg.per_kind else 3)
    assert stored["embed"] is params["embed"]
    again = llama.stored_params(stored)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(stored)))
    llama.stored_params(params, donate=True)
    for st in stacks(params).values():
        assert all(a.is_deleted() == (name in llama.ATTN_IN)
                   for name, a in st.items())


def test_a_matrix_the_compiler_reads_in_place_stays_in_its_stack(monkeypatch):
    """A layer's matrix larger than ``CUT_OUT_BYTES`` (mimo's ``wq``, 100 MB:
    the compiler streams it from where it lies, as it does a feed-forward
    matrix) keeps its published stack, leaf by leaf; the specs follow; and
    the mixed tree serves what the published tree serves."""
    cfg = llama.preset("tiny-qwen", dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    wq, wk = params["layers"]["wq"], params["layers"]["wk"]
    assert wq[0].nbytes > wk[0].nbytes
    monkeypatch.setattr(llama, "CUT_OUT_BYTES", wk[0].nbytes)
    stored = llama.stored_params(params)
    assert stored["layers"]["wq"] is wq
    assert isinstance(stored["layers"]["wk"], tuple)
    specs = llama.param_specs(cfg, 2, stored=True)["layers"]
    assert specs["wq"] == P(None, None, "tp", None)
    assert specs["wk"] == (P("tp", None),) * cfg.num_layers
    run = _forward(cfg, None)
    for got, want in zip(run(stored), run(params)):
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("preset", ["tiny-qwen", "tiny-gemma3"])
def test_the_exporter_writes_the_stored_tree_as_the_published(tmp_path,
                                                              preset):
    """``save_llama_params`` of the stored tree and of the published one
    write the same file (``q_proj.weight`` is the stored leaf's layer as it
    lies), and the loader brings it back as the published tree."""
    from safetensors import safe_open

    from dynamo_tpu.engine.loader import (load_llama_params_host,
                                          save_llama_params)

    cfg = llama.preset(preset, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    files = {}
    for name, tree in (("published", params),
                       ("stored", llama.stored_params(params))):
        save_llama_params(str(tmp_path / name), tree, cfg)
        with safe_open(str(tmp_path / name / "model.safetensors"),
                       framework="numpy") as f:
            files[name] = {k: f.get_tensor(k) for k in f.keys()}
    assert files["stored"].keys() == files["published"].keys()
    for k, v in files["published"].items():
        np.testing.assert_array_equal(files["stored"][k], v)
    q0 = files["stored"]["model.layers.0.self_attn.q_proj.weight"]
    np.testing.assert_array_equal(
        q0, np.asarray(llama.stored_params(params)["layers"]["wq"][0]))
    assert q0.shape == (cfg.num_heads * cfg.head_dim, cfg.hidden_size)
    back = load_llama_params_host(str(tmp_path / "stored"), cfg)
    for w in llama.ATTN_IN:
        np.testing.assert_array_equal(np.asarray(back["layers"][w]),
                                      np.asarray(params["layers"][w]))


def test_the_engine_stores_and_reports_the_form():
    """Whatever its source, an engine's tree holds the three projections a
    matrix [H x Dh, D] a layer, says so (``attn_proj``), and serves the
    tokens an engine handed the published tree serves; an engine with
    pipeline stages, which index the stack by a traced layer, keeps the
    published tree and says that."""
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions

    cfg = llama.preset("tiny-qwen")
    prompt = list(range(5, 40))

    def serve(published: bool):
        core = EngineCore(JaxEngineConfig(
            model=cfg, page_size=8, max_batch=2, max_context=128,
            prefill_chunk=16, decode_steps=2, warmup=False))
        lay = core.params["layers"]
        assert core.attn_proj == "out_in"
        assert [w.shape for w in lay["wq"]] == [
            (cfg.num_heads * cfg.head_dim, cfg.hidden_size)] * cfg.num_layers
        assert [w.shape for w in lay["wk"] + lay["wv"]] == [
            (cfg.num_kv_heads * cfg.head_dim,
             cfg.hidden_size)] * 2 * cfg.num_layers
        if published:
            core.params = llama.init_params(
                cfg, jax.random.PRNGKey(core.cfg.seed))
        core.submit("s", BackendInput(token_ids=prompt,
                                      stop=StopConditions(max_tokens=6)))
        got = []
        while not (got and got[-1].finish is not None):
            got += core.step()
        assert all(so.error is None for so in got)
        return [so.token for so in got]

    assert serve(False) == serve(True)
    staged = EngineCore(JaxEngineConfig(
        model=cfg, page_size=8, max_batch=2, max_context=128,
        prefill_chunk=16, decode_steps=2, warmup=False, pp=2))
    assert staged.attn_proj == "published"
    assert staged.params["layers"]["wq"].ndim == 4
