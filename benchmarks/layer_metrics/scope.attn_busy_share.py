"""Layer: kernels. Device time of ATTENTION as a share of device busy time, in
percent: the leaf seconds of the traced decode and prefill programs whose
``tf_op`` names the scope ``dynamo.attn``, ``dynamo.attn_full`` or
``dynamo.attn_window`` (``harness/scopes.py``; the kernel call and what the
program does around it under the scope: the context gather, the masks, the
transposes) over ``busy_s``. A share of busy time, not a roofline share:
nothing gives the dense cells' per-dispatch keys yet (PERF.md, Open
questions). It reads by scope and not by operation name, so a Pallas kernel
under another scope (a recurrence, a grouped matmul) is not attention's. A
capture without a device plane, and a program older than its scopes, read as
no value."""
from benchmarks.harness.scopes import KINDS, of

SCOPES = ("dynamo.attn", "dynamo.attn_full", "dynamo.attn_window")


def reduce(scrapes, trace, run):
    got = of(trace)
    if got is None or not trace.get("busy_s"):
        return None
    spent = sum(got["kinds"].get(kind, {}).get(scope, 0.0)
                for kind in KINDS.values() for scope in SCOPES)
    return 100.0 * spent / trace["busy_s"] if spent > 0 else None
