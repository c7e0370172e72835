"""Hardware specification registry.

Two devices matter to this reproduction:

* ``h100-sxm`` — the paper's measurement platform. Used by the
  paper-validation benchmarks so our analytic energy model can be checked
  against the paper's absolute and relative numbers.
* ``tpu-v5e`` — the deployment TARGET of this framework (the container is
  CPU-only; v5e constants are mandated by the roofline spec: 197 TFLOP/s
  bf16, 819 GB/s HBM, ~50 GB/s/link ICI).

Power is regime-dependent (paper §3.1: Tensor Cores "complete the
computation faster, but at a higher instantaneous power draw"):

* ``power_mxu``    — compute-bound on the matrix-unit fast path,
* ``power_scalar`` — compute-bound on the slow (fp32/CUDA-core) path,
* ``power_memory`` — memory-bound kernels (bandwidth saturated, ALUs idle),
* ``idle_power``   — dispatch gaps between kernels (~120 W on H100, §3.2).

Dispatch overhead is stack-dependent (paper §2 "Idle time": the CPU thread
issuing kernels can be slower than the GPU): the eager ``transformers``
path pays ~40 us of host work per kernel; a fused serving stack (TGI-like)
pays a few us.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class PowerState:
    """One first-class device power state on the serving timeline.

    Busy phases (prefill/decode) draw regime-dependent power computed by
    the energy model; the non-serving states here have a single nominal
    wattage the engine/cluster charge for gaps.
    """

    name: str
    power_w: float
    serves: bool = False            # can phases execute in this state?
    wake_latency_s: float = 0.0     # ramp back to a serving state


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    name: str
    # Peak dense matmul throughput for 16-bit formats (FLOP/s).
    peak_flops_16: float
    # Peak throughput for the fp32 path (FLOP/s). On H100 this is the
    # TF32/CUDA-core mix the eager stack actually achieves.
    peak_flops_32: float
    # HBM bandwidth (bytes/s).
    hbm_bw: float
    # Inter-chip link bandwidth (bytes/s per link).
    link_bw: float
    # Regime-dependent power draw (W) — see module docstring.
    power_mxu: float
    power_scalar: float
    power_memory: float
    idle_power: float
    # Host dispatch overhead per kernel launch (s), by serving stack.
    launch_overhead_eager: float
    launch_overhead_fused: float
    # Smallest efficient memory transaction (bytes). GPU: 32–64 B
    # coalescing granularity; TPU: one (8, 128) f32 tile line = 512 B.
    min_transaction_bytes: int
    # HBM capacity (bytes).
    hbm_capacity: float
    # Power draw (W) when the fleet scheduler has power-gated the chip
    # (clocks floored / low-power state). A gated chip cannot serve until
    # woken; waking costs ``wake_latency_s`` at idle power (clock ramp)
    # before the next phase can run — the cluster simulator charges it.
    gated_power: float = 40.0
    wake_latency_s: float = 0.25
    # DVFS operating point: 1.0 is the nominal (boost) clock. Derived
    # specs come from :meth:`with_freq_scale`; compute throughput scales
    # linearly with core frequency while *dynamic* power (the draw above
    # the static/idle floor) scales ~f^3 (P ∝ C·V²·f with V ∝ f). HBM
    # runs on its own clock domain, so ``hbm_bw`` and memory-bound
    # latency do not change — which is exactly why downclocking a
    # memory-bound decode saves energy nearly for free.
    freq_scale: float = 1.0
    dvfs_exponent: float = 3.0
    # Fleet autoscaling transitions. Spinning a replica up (host boot /
    # model-weights load / runtime warm-up) takes ``spinup_latency_s``
    # during which it cannot serve, and costs ``spinup_energy_j``
    # (roughly the ramp window at idle-class draw). Draining a replica
    # to off costs ``drain_latency_s`` / ``drain_energy_j``. Off draws
    # zero; the fleet simulator bills both transitions into the power
    # trace so the energy ledger still closes to 100%.
    spinup_latency_s: float = 20.0
    spinup_energy_j: float = 2400.0
    drain_latency_s: float = 5.0
    drain_energy_j: float = 600.0
    # Interconnect energy (pJ/byte) for moving state between chips —
    # what a disaggregated cluster pays to hand a prefilled KV cache
    # from a prefill replica to a decode replica. End-to-end NVLink-
    # class transfers land around O(10) pJ/bit including SerDes and
    # switch hops; TPU ICI is roughly half that. Handoff latency uses
    # ``link_bw`` (sender-side single link, the conservative bound).
    link_pj_per_byte: float = 80.0

    def peak_flops(self, bits: float) -> float:
        """Matmul peak for a given operand width (compute side).

        Integer formats are dequantized to 16-bit before the matmul on
        both platforms (bitsandbytes on GPU, our quant_matmul on TPU), so
        compute peak is the 16-bit peak for everything except fp32.
        """
        return self.peak_flops_32 if bits >= 32 else self.peak_flops_16

    def compute_power(self, bits: float) -> float:
        return self.power_scalar if bits >= 32 else self.power_mxu

    def launch_overhead(self, stack: str) -> float:
        return (self.launch_overhead_fused if stack == "fused"
                else self.launch_overhead_eager)

    def power_states(self) -> Dict[str, PowerState]:
        """First-class power states of this device: the serving
        ``active`` state (regime-dependent draw — the listed wattage is
        the MXU ceiling) plus the non-serving ``idle`` and ``gated``
        states the engine/cluster charge for gaps."""
        return {
            "active": PowerState("active", self.power_mxu, serves=True),
            "idle": PowerState("idle", self.idle_power),
            "gated": PowerState("gated", self.gated_power,
                                wake_latency_s=self.wake_latency_s),
            "off": PowerState("off", 0.0,
                              wake_latency_s=self.spinup_latency_s),
        }

    def state_power(self, state: str) -> float:
        """Nominal power draw (W) for a non-busy power state on the
        serving timeline (:mod:`repro.serving.trace`). Busy states
        (prefill/decode) are regime-dependent and carry their own
        energy, so they have no single nominal wattage here."""
        st = self.power_states().get(state)
        if st is None or st.serves:
            raise ValueError(f"no nominal power for state {state!r}")
        return st.power_w

    def with_freq_scale(self, scale: float) -> "DeviceSpec":
        """Derive the spec for a DVFS operating point at ``scale`` of
        the *current* core clock.

        Compute throughput scales linearly; busy power scales as
        ``idle + (P - idle) * scale**dvfs_exponent`` (the static/leakage
        floor — approximated by ``idle_power`` — does not clock down);
        HBM bandwidth, host launch overhead, and the idle/gated states
        live on other clock/voltage domains and are unchanged.

        Repeated application composes multiplicatively and exactly:
        ``spec.with_freq_scale(a).with_freq_scale(b)`` is the operating
        point at ``a*b`` of nominal, because the dynamic-power law is
        multiplicative above the shared idle floor — so a controller may
        re-apply relative scales mid-run without drift. The combined
        operating point must stay within [0.1, 1.5] of nominal.
        """
        if scale <= 0:
            raise ValueError(f"freq_scale must be positive, got {scale}")
        if scale == 1.0:
            return self
        combined = self.freq_scale * scale
        if not 0.1 <= combined <= 1.5:
            raise ValueError(
                f"freq_scale {combined:g} (= {self.freq_scale:g} * "
                f"{scale:g}) outside [0.1, 1.5]")

        def dyn(p: float) -> float:
            return (self.idle_power
                    + (p - self.idle_power) * scale ** self.dvfs_exponent)

        base = self.name.split("@f")[0]
        name = base if combined == 1.0 else f"{base}@f{combined:g}"
        return dataclasses.replace(
            self, name=name,
            peak_flops_16=self.peak_flops_16 * scale,
            peak_flops_32=self.peak_flops_32 * scale,
            power_mxu=dyn(self.power_mxu),
            power_scalar=dyn(self.power_scalar),
            power_memory=dyn(self.power_memory),
            freq_scale=combined)


H100_SXM = DeviceSpec(
    name="h100-sxm",
    peak_flops_16=989e12,       # dense bf16/fp16 tensor core
    peak_flops_32=99e12,        # eager fp32 path (TF32-assisted, ~10x slower
                                # than the TC path — matches paper Fig 4)
    hbm_bw=3.35e12,
    link_bw=450e9 / 18,         # NVLink per-link
    power_mxu=700.0,
    power_scalar=280.0,         # paper: ~4x energy gain at ~10x latency gain
    power_memory=350.0,
    idle_power=120.0,           # paper §3.2: "typically around 120 W"
    launch_overhead_eager=40e-6,  # transformers host loop per kernel
    launch_overhead_fused=5e-6,   # TGI/CUDA-graph-ish dispatch
    min_transaction_bytes=64,
    hbm_capacity=80e9,
    gated_power=45.0,           # deep low-power state, well under 120 W idle
    wake_latency_s=0.25,        # clock/power ramp back to serving state
    spinup_latency_s=30.0,      # weights load + runtime warm-up
    spinup_energy_j=3600.0,     # ~idle-class draw over the ramp window
    drain_latency_s=5.0,
    drain_energy_j=600.0,
    link_pj_per_byte=80.0,      # NVLink end-to-end (~10 pJ/bit)
)

TPU_V5E = DeviceSpec(
    name="tpu-v5e",
    peak_flops_16=197e12,       # mandated constant
    peak_flops_32=197e12 / 4,   # fp32 through MXU at 1/4 rate
    hbm_bw=819e9,               # mandated constant
    link_bw=50e9,               # mandated constant, per link
    power_mxu=200.0,            # ~v5e chip TDP class
    power_scalar=120.0,
    power_memory=110.0,
    idle_power=60.0,
    launch_overhead_eager=10e-6,  # per-step host dispatch gap (XLA runs one
    launch_overhead_fused=2e-6,   # fused program per step)
    min_transaction_bytes=512,    # one 8x128 f32 tile row
    hbm_capacity=16e9,
    gated_power=15.0,
    wake_latency_s=0.1,
    spinup_latency_s=15.0,      # smaller weights shard per chip
    spinup_energy_j=900.0,
    drain_latency_s=3.0,
    drain_energy_j=180.0,
    link_pj_per_byte=40.0,      # ICI, shorter reach than NVLink
)

DEVICES = {d.name: d for d in (H100_SXM, TPU_V5E)}

#: ``jax.Device.device_kind`` -> the DeviceSpec of that chip; the one
#: place a real accelerator is mapped to its constants (a v5e reports
#: itself as "TPU v5 lite")
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def get_device(name: str) -> DeviceSpec:
    try:
        return DEVICES[name]
    except KeyError:
        raise ValueError(f"unknown device {name!r}; known: {list(DEVICES)}")


def device_for_kind(kind: str) -> DeviceSpec:
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(f"no DeviceSpec for device_kind {kind!r}; "
                         f"known: {sorted(DEVICE_KINDS)}")


def check_executed_device(device: DeviceSpec, platform: str,
                          kind: str) -> None:
    """Refuse an executed run on a TPU that bills another chip's
    constants (a DVFS-scaled spec counts as its base chip). Runs on
    other platforms are not checked."""
    if platform != "tpu":
        return
    chip = device_for_kind(kind)
    if device.name.split("@f")[0] != chip.name:
        raise ValueError(
            f"executed run on {kind!r} was given device={device.name!r}; "
            f"this chip is device={chip.name!r}")
