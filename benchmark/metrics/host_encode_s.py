"""Host seconds of the encode layer per aggregate (ops/encoding.py,
ops/wirecodec.py, native/row_packer.cc, the host side of
ops/streaming.py): the main-thread stages folded in _stages.py."""

from benchmark.metrics import _stages


def read(ctx):
    return _stages.mean_over_items(
        ctx, lambda st: _stages.host_encode_s(st) or None)
