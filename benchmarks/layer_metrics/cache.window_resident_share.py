"""Layer: KV cache. Tokens the window cache holds for the lanes of the
window's decode dispatches, as a share of the tokens the same lanes hold in
the global cache (their whole contexts), in percent: delta
``dyn_kv_resident_token_steps_total{pool="window"}`` / delta
``...{pool="global"}``. A uniform cache would read 100 in every layer; the
lower it reads, the more of a mostly-windowed model's cache the second page
pool does not keep (pages of 64 tokens around a window of 128: 128-191
tokens a lane, whatever its context)."""
from benchmarks.harness.kinds import RESIDENT
from benchmarks.harness.launch import delta


def reduce(scrapes, trace, run):
    held = {pool: delta(scrapes["before"], scrapes["after"], RESIDENT,
                        pool=pool) for pool in ("window", "global")}
    if held["global"] <= 0:
        return None
    return 100.0 * held["window"] / held["global"]
