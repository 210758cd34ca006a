"""What the benchmark reads from the device and the runtime itself:
which chip it holds, its peak memory, and the programs JAX lowers."""
from __future__ import annotations

import os


def keep_logs_in(root: str) -> None:
    """The TPU runtime's logs go inside the checkout, not to a fixed
    path outside it (call before JAX starts)."""
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(root, "perfbench_out", "tpu_logs"))


class CompileCounter:
    """Counts programs JAX lowers (each compile or cache load) while
    armed: the window must see none."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name == self.EVENT:
            self.count += 1


def look_for_chip(chips: int):
    """(device record, devices, peaks) of a TPU host with ``chips``
    chips, or SystemExit."""
    import jax

    from perfbench.peaks import peaks_for

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"perfbench: no TPU: JAX's first device is {d0.platform} "
                         f"({d0.device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"perfbench: {len(devs)} chips visible, the cell asks for {chips}")
    try:
        peaks = peaks_for(d0.device_kind)
    except KeyError as e:
        raise SystemExit(f"perfbench: {e.args[0]}") from None
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}, \
        devs[:chips], peaks


def enable_cache():
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, goes to the cache: the
    # second run of a cell then loads instead of compiling
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak(devices) -> int:
    """Peak device memory of the fullest chip: the allocator's peak of
    live buffers plus its peak of memory reserved for programs'
    temporaries. On the TPU ``peak_bytes_in_use`` alone leaves out a
    running program's temporaries, which the runtime reserves apart
    (``peak_bytes_reserved``); the sum bounds the true peak from above
    and matches the compiled step's ``peak_memory_in_bytes``."""
    def one(d):
        st = d.memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0)) + int(st.get("peak_bytes_reserved", 0))

    return max(one(d) for d in devices)
