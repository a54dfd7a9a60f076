#!/usr/bin/env python3
"""Builder's tool, on the chip: what a tree SERVES under one seed, kept to
the bit, so that two trees can be held equal.

    cd <tree> && python <this file> --out <file.npz> [--lengths 4100,9023] [config ...]
    python <this file> --compare <a.npz> <b.npz>

Imports ``dynamo_tpu`` from the CURRENT directory. For a benchmark
configuration (``benchmarks/configs/<name>.json``: widths, depth, engine
arguments, weights seeded as the benchmark seeds them) it builds the engine
in this process, without warm-up, serves a handful of prompts of lengths
around page and block edges at once (so decode runs them as lanes beside
lanes it does not serve) and keeps every token and its log-probability
(float32). ``--lengths`` serves prompts of those lengths instead (PR 43: a
4k and a 9k prompt through the latent configuration's chunk programs up to
its 16k bucket). ``--compare`` holds two such files equal bit for bit. PERF.md
section 6, PR 41. ``REHEARSE=1 JAX_PLATFORMS=cpu ... tiny-byte`` runs a
preset at toy size (a rehearsal of the script).
"""

import json
import os
import sys

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

SEED = 2147410041
# prompt lengths (tokens): under a page (64), a page + 1, a block of 8 pages
# and one more, over a window of 128, several blocks
LENGTHS = [5, 65, 130, 511, 513, 900]
NEW = 24


def serve(name, lengths=LENGTHS):
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
    from dynamo_tpu.models import llama
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    if os.environ.get("REHEARSE"):
        model, eng = llama.preset(name), {"max_batch": 8, "max_context": 1024,
                                          "prefill_chunk": 64, "page_size": 64}
    else:
        with open(os.path.join("benchmarks", "configs", name + ".json")) as f:
            config = json.load(f)
        eng = config["benchmark"]["engine"]
        model = llama.LlamaConfig.from_hf_config(
            {k: v for k, v in config.items() if k != "benchmark"})
    core = EngineCore(JaxEngineConfig(model=model, attn_impl="auto",
                                      seed=SEED % (1 << 31), warmup=False,
                                      **eng))
    rng = np.random.default_rng(SEED)
    names = []
    for n in lengths:
        if n + NEW > eng["max_context"]:
            continue
        names.append(f"p{n}")
        core.submit(names[-1], BackendInput(
            token_ids=[int(t) for t in rng.integers(3, model.vocab_size, n)],
            stop=StopConditions(max_tokens=NEW, ignore_eos=True)))
    got = {n: [] for n in names}
    done = set()
    for _ in range(5000):
        for so in core.step():
            got[so.seq_id].append((so.token, so.token_logprob))
            if so.finish is not None:
                done.add(so.seq_id)
        if len(done) == len(names) and not core.has_work:
            break
    assert len(done) == len(names), done
    kept = {}
    for n in names:
        kept[f"{name}.{n}.tokens"] = np.asarray([t for t, _ in got[n]],
                                                np.int32)
        kept[f"{name}.{n}.logprobs"] = np.asarray([p for _, p in got[n]],
                                                  np.float32)
    said = {"paged_kernel": core.paged_kernel,
            "decode_kv_write": core.decode_kv_write,
            "tokens": int(sum(len(v) for v in got.values())),
            "logprob_sum": float(sum(p for v in got.values() for _, p in v))}
    live = getattr(core.stage, "attn_pages_live", None)
    if live is not None:
        said["pages"] = {k[0]: (v, core.stage.attn_pages_visited.get(k[0]))
                         for k, v in live._values.items()}
    return said, kept


def main(argv) -> int:
    if argv[:1] == ["--compare"]:
        a, b = (np.load(f) for f in argv[1:3])
        same = {n: bool(np.array_equal(a[n].view(np.int32),
                                       b[n].view(np.int32)))
                for n in a.files if n in b.files}
        print(json.dumps({"same": sum(same.values()), "of": len(same),
                          "differ": [n for n, s in same.items() if not s]}))
        return 0 if all(same.values()) and set(a.files) == set(b.files) else 1
    out = None
    if argv[:1] == ["--out"]:
        out, argv = argv[1], argv[2:]
    lengths = LENGTHS
    if argv[:1] == ["--lengths"]:
        lengths, argv = [int(n) for n in argv[1].split(",")], argv[2:]
    kept = {}
    for name in argv:
        said, arrays = serve(name, lengths)
        print(name, json.dumps(said), flush=True)
        kept.update(arrays)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        np.savez_compressed(out, **kept)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
