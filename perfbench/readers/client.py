"""A value the driver measured at its own client (the host's clock),
recorded beside the end-to-end metrics and not judged.  Spec: ``value``."""


def read(rec, spec):
    return rec.values.get(spec["value"])
