"""Hardware probe (paper Fig 7a: ``cpuinfo.get_cpu_info()['flags']`` feeding
``--targets``). Here: query the live JAX backend and map it to an SRU name.
The generator can also be "tricked into assuming specific hardware"
(paper §4.1) by passing explicit flags — that is exactly how we generate the
TPU library on this CPU-only container.

``auto`` resolves into the UPD-defined SRU family (tsl_data/targets/):
cpu_xla on CPU (and GPU, conservatively) hosts, and on a TPU the target its
exact ``device_kind`` maps to in :data:`TPU_TARGETS`. A TPU kind that is not
in the table is an error: a library tuned for another chip would run with
the wrong tiling and roofline constants. Flag sets are NOT duplicated here —
the probed SRU's own ``lscpu_flags`` from the UPD are the single source of
truth."""

from __future__ import annotations

import jax


# jax.devices()[0].device_kind -> UPD target name
TPU_TARGETS = {"TPU v5 lite": "tpu_v5e"}


def live_target() -> str:
    backend = jax.default_backend()
    if backend == "tpu":
        kind = jax.devices()[0].device_kind
        if kind not in TPU_TARGETS:
            raise RuntimeError(
                f"no UPD target for TPU device kind {kind!r}; known kinds: "
                f"{sorted(TPU_TARGETS)}")
        return TPU_TARGETS[kind]
    # cpu, and conservatively gpu: the portable XLA dialect
    return "cpu_xla"


def runnable(target) -> bool:
    """Can the code generated for ``target`` (a TargetDef) execute in this
    process: a host-runnable dialect, or the live device's own target."""
    return target.runs_on_host or target.name == live_target()


def live_flags() -> tuple[str, ...]:
    """Feature flags of the probed SRU, read from its UPD target document."""
    from . import loader

    name = live_target()
    for doc in loader.load_raw_targets():
        if doc.get("name") == name:
            return tuple(sorted(doc.get("lscpu_flags", ())))
    return ("xla",)
