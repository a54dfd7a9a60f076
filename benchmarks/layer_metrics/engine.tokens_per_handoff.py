"""Layer: engine scheduler. Tokens the engine thread hands to the event
loop per cross-thread call: delta ``dyn_engine_handoff_tokens_total`` / delta
``dyn_engine_handoffs_total``. One call a token reads 1; one call an
iteration reads about active lanes x ``decode_steps`` with every lane
decoding, and with it the loop thread's work (one queue item a lane) stops
growing with the tokens in flight. A program without the counter reads as no
value."""
from benchmarks.harness.launch import delta

HANDOFFS = "dyn_engine_handoffs_total"
TOKENS = "dyn_engine_handoff_tokens_total"


def reduce(scrapes, trace, run):
    b, a = scrapes["before"], scrapes["after"]
    if not any(name == HANDOFFS for name, _, _ in a):
        return None
    n = delta(b, a, HANDOFFS)
    if n <= 0:
        return None
    return delta(b, a, TOKENS) / n
