"""The part of set-up that assembles the device-resident state after binning:
the ``gbdt.fit.upload`` span of the fit (host time; the transfers run
behind it)."""
from benchmark import span_read


def read(**_):
    return span_read.children_seconds("gbdt.fit", "gbdt.fit.upload")
