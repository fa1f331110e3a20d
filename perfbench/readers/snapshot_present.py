"""``snapshot``'s kinds for a counter that the program under test may
not keep, as the parent of the PR that adds the counter does not: a
path missing from the program's snapshot reads as nothing to report,
not as an error.  The spec is ``snapshot``'s."""
from perfbench.readers import snapshot


def read(rec, spec):
    try:
        return snapshot.read(rec, spec)
    except KeyError:
        return None
