"""A test-only family: the Chimera dataplane family with each hook recorded
as it is called, with the files on the caller's stack, so that a test can
show the harness reaches every architecture hook through the family."""

import traceback

from lib import spec

_CHIMERA = spec.load_module("models", "chimera_dataplane")
HOOKS = ("arch_config", "make_params", "token_flops", "row_bytes", "weight_bytes")
calls = []  # (hook, files on the stack), in call order


def _recorded(name):
    hook = getattr(_CHIMERA, name)

    def call(*args, **kwargs):
        calls.append((name, [f.filename for f in traceback.extract_stack()[:-1]]))
        return hook(*args, **kwargs)

    return call


arch_config, make_params, token_flops, row_bytes, weight_bytes = map(_recorded, HOOKS)
