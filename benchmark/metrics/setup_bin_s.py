"""The part of set-up that bins on the host: the ``gbdt.fit.bin`` spans of the
fit (the bin mapper, then the rows chunk by chunk, each chunk's upload
enqueued as it is binned)."""
from benchmark import span_read


def read(**_):
    return span_read.children_seconds("gbdt.fit", "gbdt.fit.bin")
