"""Of the time the window's requests spent decoding (first token to last,
``decode_s`` of the ``serving.request`` spans), the share the decode loop
spent inside OTHER requests' admissions and resumes between their tokens
(``stalled_s``): what a decoding user loses to other people's prefills.  Over
the requests that finished inside the window.  Wall time on the host's clock:
an admission first waits for the decode step in flight, so a request's
``stalled_s`` holds up to one step an admission more than the device's
prefill time, and the share reads over ``prefill_device_share``.  A program
whose request spans carry no account gives nothing to read."""
from benchmark import span_read


def read(facts, **_):
    t0, t1 = facts.get("t0"), facts.get("t1")
    done = [s.attrs for s in span_read.spans("serving.request")
            if "decode_s" in s.attrs and "stalled_s" in s.attrs
            and (t0 is None or t0 <= s.end_ns / 1e9 < t1)]
    decode = sum(a["decode_s"] for a in done)
    if not decode:
        return None
    return 100.0 * sum(a["stalled_s"] for a in done) / decode
