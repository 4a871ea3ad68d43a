"""The comparison that decides ``correct``: the system's rows against
the plain reference's.  Integers, strings, dates and counts must be
equal; an f64 may differ by the configuration's relative tolerance
(the chip holds an f64 as two f32, so bit equality is not on offer)."""
import datetime
import math

EPOCH = datetime.date(1970, 1, 1)


def _sort_key(row):
    return tuple((v is None, str(type(v)), v if v is not None else 0)
                 for v in row)


def plain(v):
    """A numpy scalar as the Python value it holds; a date as the days
    since 1970 that the system's rows carry for a DATE32."""
    if hasattr(v, "item"):
        v = v.item()
    if isinstance(v, datetime.date) and not isinstance(v, datetime.datetime):
        v = (v - EPOCH).days
    return v


def value_equal(want, got, rel_tol):
    want, got = plain(want), plain(got)
    if want is None or got is None:
        return want is None and got is None
    if isinstance(want, float) or isinstance(got, float):
        if isinstance(want, bool) or isinstance(got, bool):
            return False
        want, got = float(want), float(got)
        if math.isnan(want) or math.isnan(got):
            return math.isnan(want) and math.isnan(got)
        return math.isclose(want, got, rel_tol=rel_tol, abs_tol=0.0)
    return type(want) is type(got) and want == got


def difference(want_rows, got_rows, ordered, rel_tol):
    """None where the answers agree, else one line saying where not."""
    if len(want_rows) != len(got_rows):
        return f"{len(got_rows)} rows, the reference has {len(want_rows)}"
    want_rows = [tuple(r) for r in want_rows]
    got_rows = [tuple(r) for r in got_rows]
    if not ordered:
        want_rows = sorted(want_rows, key=_sort_key)
        got_rows = sorted(got_rows, key=_sort_key)
    for i, (w, g) in enumerate(zip(want_rows, got_rows)):
        if len(w) != len(g):
            return f"row {i} has {len(g)} columns, the reference {len(w)}"
        for j, (a, b) in enumerate(zip(w, g)):
            if not value_equal(a, b, rel_tol):
                return (f"row {i} column {j}: got {b!r}, "
                        f"the reference has {a!r}")
    return None
