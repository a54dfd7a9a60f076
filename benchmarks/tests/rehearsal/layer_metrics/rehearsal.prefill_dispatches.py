"""A throw-away per-layer metric: prefill dispatches the trace names
(host annotations ``dynamo.prefill[...]``); nothing to read -> nothing."""


def reduce(scrapes, trace, run):
    if not trace:
        return None
    n = trace.get("annotations", {}).get("dynamo.prefill")
    return n if n else None
