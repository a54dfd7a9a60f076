"""Layer: kernels. Key blocks the latent flash call copied, as a share of
the key blocks of its grid, in percent, over the whole window: delta
``dyn_attn_latent_key_blocks_total{kind="prefill", state="copied"}`` / delta
``...{state="bucket"}`` (``docs/observability.md``; one layer's worth, every
lane and query block of each chunk program). The grid is laid over the
program's context bucket, a power of two; a (query block, key block) pair in
which no query sees a valid key is neither copied nor computed on since PR
43, so this reads how much of the bucket the chunks' contexts filled: the
part of the grid that still costs a copy and its arithmetic, the rest a grid
step's overhead. It moves with the traffic (where prompts end in their
buckets) as well as with the kernel. A program without the counter (a parent
commit from before it: every block copied, nothing counted) and a window
without a chunk read as no value."""
from benchmarks.harness.launch import delta

BLOCKS = "dyn_attn_latent_key_blocks_total"


def reduce(scrapes, trace, run):
    of = lambda state: delta(scrapes["before"], scrapes["after"], BLOCKS,
                             kind="prefill", state=state)
    bucket = of("bucket")
    if bucket <= 0:
        return None
    return 100.0 * of("copied") / bucket
