"""The widest gap between two of a request's tokens (``gap_max_s`` of the
``serving.request`` spans: the longest period between two decode steps'
returns in the request's life, or the time from its first token to the
first step that gave it another), at the 95th percentile over the requests
that finished inside the window: the freeze a reader of a streamed answer
sees in the middle of it.  Where fewer than 30 requests finished the number
is their MAXIMUM, and the line on standard error says which it was."""
import numpy as np

from benchmark import harness, span_read

ENOUGH = 30


def read(facts, **_):
    t0, t1 = facts.get("t0"), facts.get("t1")
    gaps = [s.attrs["gap_max_s"] for s in span_read.spans("serving.request")
            if "gap_max_s" in s.attrs
            and (t0 is None or t0 <= s.end_ns / 1e9 < t1)]
    if not gaps:
        return None
    tail = len(gaps) >= ENOUGH
    harness.say(f"token_gap_max_p95_ms: {'p95' if tail else 'maximum'} of "
                f"{len(gaps)} requests")
    return 1e3 * float(np.percentile(gaps, 95) if tail else max(gaps))
