"""The allocator's bytes at the exit of a depth-0 span of the program's tree
(``lib/spans.py``), in GiB: the program's tracer samples ``bytes_in_use``
and ``peak_bytes_in_use`` there (``hbm_in_use_bytes``, ``hbm_peak_bytes``,
the largest over the chips). ``at`` names the span, ``LAST`` set-up's last;
``rise`` subtracts the same ``field`` at the exit of the span it names, or of
the depth-0 span before ``at`` (``BEFORE``). The peak only rises, so where
it stands at each phase's end says which phase set it.

Into the run's details goes ``hbm_by_phase``: name, bytes in use and peak of
every depth-0 span that carries them, in order, through the window's blocks.
A span the run did not record, or one without the sample (a backend that
keeps no ``memory_stats``): nothing returned, and listed in
``spans_missing``."""

from benchmarks.lib import spans as sp

GIB = 2.0 ** 30
LAST, BEFORE = "(last of set-up)", "(span before)"


def read(ctx, at, field, rise=None):
    tree = sp.tree_of(ctx)
    roots = sp.setup_roots(tree)
    sampled = [s for s in tree if s.depth == 0 and field in s.args]
    if sampled:
        ctx["details"]["hbm_by_phase"] = [
            {"name": s.name, "in_use": s.args.get("hbm_in_use_bytes"),
             "peak": s.args.get("hbm_peak_bytes")} for s in sampled]

    def exit_of(name):
        if name == LAST:
            found = roots[-1:]
        else:
            found = [s for s in roots if s.name == name][-1:]
        if not found or field not in found[0].args:
            sp.missing(ctx, name)
            return None
        return found[0]

    span = exit_of(at)
    if span is None:
        return None
    value = span.args[field]
    if rise is not None:
        i = roots.index(span)
        base = exit_of(roots[i - 1].name if rise == BEFORE and i else rise)
        if base is None:
            return None
        value -= base.args[field]
    return value / GIB
