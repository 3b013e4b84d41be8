"""Reference implementation of the ``flow_ingest`` family.

The fused whole-batch ingest is *structural*: an on-device chunk loop whose
body is the very :func:`repro.serve.flow_engine.make_flow_step` step the
per-round engine jits.  The reference backend therefore has no separate
oracle body — it IS the per-round step, scanned on device, which makes it
bit-exact to the legacy path by construction (the family's conformance
contract).  The Pallas backends (``kernel.py``) swap only the streaming
score stage; everything else is shared with this builder.

``tiles`` is accepted for signature uniformity and ignored — the reference
path has no tile knobs.
"""

from __future__ import annotations


def fused_ingest_ref(ccfg, n_slots: int, max_len: int, int_plan=None, *,
                     tiles=None):
    from repro.serve.flow_engine import make_fused_ingest

    del tiles  # performance knob of the Pallas backends only
    return make_fused_ingest(ccfg, n_slots, max_len, int_plan=int_plan)
