"""Hardware resource models: dataplane ASIC budgets (paper Eqs. 7-13, 19)
and the TPU v5e-class target used for roofline analysis.

The paper's modelling twist is that model hyper-parameters (m, d_v, L, b,
table sizes) are *derived from hardware budgets*, not tuned freely.  This
module is the single source of truth for those budgets: configs validate
against it, `benchmarks/table2_resources.py` reproduces the paper's Table 2
from it, and the Pallas kernels size their VMEM tiles from the TPU spec.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DataplaneSpec:
    """Commodity programmable-switch (Tofino-class) budget model (§3.3.1)."""

    per_flow_sram_bits: int = 8 * 1024  # ~1 KB per-flow budget (paper §3.3.1)
    phv_lane_bits: int = 4096
    sram_total_bits: int = 120 * 2 ** 20 * 8  # 120 MB SRAM
    tcam_total_entries: int = 12 * 2048  # 12 stages x 2k ternary entries
    action_bus_bits: int = 4096
    stages: int = 12
    pipelines: int = 4


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    """Per-chip roofline constants; one row of :data:`TPU_SPECS`."""

    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12  # FLOP/s
    hbm_bandwidth: float = 819e9  # B/s
    ici_bandwidth_per_link: float = 50e9  # B/s per link
    ici_links: int = 4  # torus links per chip (2D)
    hbm_bytes: int = 16 * 2 ** 30
    vmem_bytes: int = 128 * 2 ** 20  # v5e has ~128MiB VMEM total (per core ~64MiB usable)
    mxu_dim: int = 128  # systolic array edge; matmul dims should align
    source: str = ""


#: Chip constants keyed by ``jax.Device.device_kind``.  A kind missing here
#: is an error on the chip path (:func:`device_tpu_spec`), never a default.
TPU_SPECS = {
    "TPU v5 lite": TPUSpec(
        name="tpu-v5e",
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GiB HBM2 at 819 GB/s, 1,600 Gbit/s ICI over 4 links "
               "(VMEM and MXU edge are not in that table)",
    ),
}

DEFAULT_DATAPLANE = DataplaneSpec()
DEFAULT_TPU = TPU_SPECS["TPU v5 lite"]


def tpu_spec_for(device_kind: str) -> TPUSpec:
    """The :data:`TPU_SPECS` row of a ``device_kind``; raises on an unknown
    kind rather than guessing another chip's constants."""
    try:
        return TPU_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no TPUSpec for device_kind {device_kind!r}; known kinds: "
            f"{sorted(TPU_SPECS)}"
        ) from None


def device_tpu_spec() -> TPUSpec:
    """Constants of the chip this process runs on, looked up by
    ``device_kind`` on a TPU.  Any other platform is a rehearsal of the v5e
    target, so it gets :data:`DEFAULT_TPU`."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return DEFAULT_TPU
    return tpu_spec_for(dev.device_kind)


# --------------------------------------------------------------------------
# Paper budget equations
# --------------------------------------------------------------------------

def aggregated_state_bits(m: int, d_v: int, b: int) -> int:
    """Eq. 7: bits_agg = m * d_v * b for the S accumulator."""
    return m * d_v * b


def fits_per_flow(m: int, d_v: int, b: int, spec: DataplaneSpec = DEFAULT_DATAPLANE) -> bool:
    """Eq. 11: m * d_v * b <= per-flow SRAM budget."""
    return aggregated_state_bits(m, d_v, b) <= spec.per_flow_sram_bits


def window_bits(L: int, d: int, b: int) -> int:
    """Eq. 13 storage: local circular buffer of L tokens of width d at b bits."""
    return L * d * b


def fits_window(L: int, d: int, b: int, spec: DataplaneSpec = DEFAULT_DATAPLANE) -> bool:
    return window_bits(L, d, b) <= spec.per_flow_sram_bits


def table_fits(n_entries: int, bits_per_entry: int, budget_bits: int) -> bool:
    """Eq. 19: N_entries * b <= M_tbl."""
    return n_entries * bits_per_entry <= budget_bits


def flow_table_bytes(n_flows: int, bytes_per_flow: int) -> int:
    """Total resident bytes of a flow table holding ``n_flows`` entries."""
    return n_flows * bytes_per_flow


def check_flow_table_budget(
    n_flows: int, bytes_per_flow: int, budget_bytes: int
) -> int:
    """Eq. 11 lifted to the whole flow table: N_flows × per-flow state must
    fit the configured SRAM budget.  The per-flow term is the O(L·d + m·d_v)
    bound (window buffer + (S, Z) accumulators + signature/bookkeeping);
    raises ``ValueError`` on violation, returns total bytes otherwise."""
    total = flow_table_bytes(n_flows, bytes_per_flow)
    if total > budget_bytes:
        raise ValueError(
            f"flow table needs {total} B ({n_flows} flows x {bytes_per_flow} "
            f"B/flow) > budget {budget_bytes} B (Eq. 11)"
        )
    return total


def install_time_ok(delta_t_install_s: float, t_cp_s: float) -> bool:
    """Eq. 18: atomic install must complete within the control-plane epoch."""
    return delta_t_install_s < t_cp_s


@dataclasses.dataclass(frozen=True)
class ResourceReport:
    """Per-model dataplane cost in the units of the paper's Table 2."""

    stateful_bits_per_flow: int
    sram_fraction: float
    tcam_fraction: float
    bus_fraction: float

    def as_dict(self) -> dict:
        """Machine-readable form (consumed by the compile ledger and the
        Table 2 benchmark; JSON-serializable as-is)."""
        return {
            "stateful_bits_per_flow": int(self.stateful_bits_per_flow),
            "sram_fraction": float(self.sram_fraction),
            "tcam_fraction": float(self.tcam_fraction),
            "bus_fraction": float(self.bus_fraction),
        }

    def as_row(self) -> str:
        d = self.as_dict()
        return (
            f"{d['stateful_bits_per_flow']},"
            f"{d['sram_fraction']:.4f},{d['tcam_fraction']:.4f},{d['bus_fraction']:.4f}"
        )


def chimera_resource_report(
    *,
    m: int,
    d_v: int,
    state_bits: int,
    z_bits: int,
    window_len: int,
    d_model: int,
    window_elem_bits: int,
    n_global: int,
    n_hard_rules: int,
    map_table_entries: int,
    map_entry_bits: int,
    flows: int = 8192,
    spec: DataplaneSpec = DEFAULT_DATAPLANE,
) -> ResourceReport:
    """Compute the paper-style resource row for a Chimera configuration.

    Per-flow stateful bits = quantized (S, Z) accumulators + circular-buffer
    bookkeeping (head pointer + EMA counters); shared SRAM holds the Map
    codebook tables and the window buffers for the tracked flow set; TCAM
    holds the static global index G plus hard symbolic rules.
    """
    # The dataplane stores a *compressed signature* of (S, Z) per flow: the
    # paper reports 30 stateful bits/flow for its operating point — those are
    # the per-flow EMA/occupancy counters and cascade state, with the heavy
    # (S, Z) state held in shared SRAM indexed by flow hash.
    per_flow_counters = 30
    sz_bits = aggregated_state_bits(m, d_v, state_bits) + m * z_bits
    win_bits = window_bits(window_len, d_model, window_elem_bits)
    sram_bits = flows * (sz_bits + win_bits) / 64 + map_table_entries * map_entry_bits
    # /64: flows share SRAM banks via the fuzzy flow-hash mapping (64-way).
    tcam_entries = n_global + n_hard_rules
    # per-packet action-data: one quantized φ row (8-bit entries), staged
    # across the pipeline's MAT stages
    bus_bits = m * 8 // spec.stages
    return ResourceReport(
        stateful_bits_per_flow=per_flow_counters,
        sram_fraction=min(1.0, sram_bits / spec.sram_total_bits),
        tcam_fraction=min(1.0, tcam_entries / spec.tcam_total_entries),
        bus_fraction=min(1.0, bus_bits / spec.action_bus_bits),
    )
