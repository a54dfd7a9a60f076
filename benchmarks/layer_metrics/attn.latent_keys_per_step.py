"""Layer: kernels. Latent rows a decode step read, all its lanes, one
layer's worth, over the whole window: delta
``dyn_attn_latent_keys_total{kind="decode"}`` / (delta
``dyn_engine_dispatches_total{kind="decode"}`` x ``decode_steps``). What the
latent kernel's roofline share and the step's share are shares OF: 1,152 B
and 278,528 operations a row and layer. A DENOMINATOR, not a score: it
moves with the traffic alone (the lanes in decode and their contexts), so
its direction in the manifest, which wants one of every metric, says
nothing: never read a change of it as a gain or a loss."""
from benchmarks.harness.latent import keys_per_step


def reduce(scrapes, trace, run):
    return keys_per_step(scrapes, run)
