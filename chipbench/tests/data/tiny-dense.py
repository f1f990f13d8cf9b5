"""Family ``tiny-dense``, for the benchmark's own tests: the ``dense``
family's functions, each recording its name in ``CALLED`` when it runs,
so a test sees that a run takes the family its configuration names."""
from pathlib import Path

from harness import spec

_dense = spec.family(Path(__file__).resolve().parents[2], "dense")
CALLED = set()
MATRICES = _dense.MATRICES


def _recorded(name):
    f = getattr(_dense, name)

    def call(*args, **kw):
        CALLED.add(name)
        return f(*args, **kw)

    call.__name__ = name
    return call


shapes = _recorded("shapes")
program_tree = _recorded("program_tree")
program_keys = _recorded("program_keys")
layer = _recorded("layer")
matmul_params_per_layer = _recorded("matmul_params_per_layer")
