"""Pieces the decoder builders share: a projection without bias, and the
columns of one projection split into parts, so that a published tensor
that packs several projections loads as it is."""

from math import gcd

from .. import symbol as sym


def linear(data, width, name):
    return sym.FullyConnected(data=data, num_hidden=width, no_bias=True,
                              name=name)


def columns(data, widths, name):
    """The columns of ``data`` cut into consecutive parts, ``widths``
    ``{suffix: width}`` in order: equal slices (``SliceChannel`` cuts
    equal parts) put back together, a part of several slices as the
    ``Concat`` ``<name>_<suffix>``."""
    part = gcd(*widths.values())
    parts = sym.SliceChannel(data=data,
                             num_outputs=sum(widths.values()) // part,
                             axis=1, name=name)
    out, lo = [], 0
    for suffix, width in widths.items():
        hi = lo + width // part
        out.append(parts[lo] if hi - lo == 1 else sym.Concat(
            *[parts[i] for i in range(lo, hi)], dim=1,
            name=f"{name}_{suffix}"))
        lo = hi
    return out
