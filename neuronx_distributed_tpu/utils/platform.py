"""Platform helpers (the TPU-stack analogue of the reference's
``NXD_CPU_MODE`` switch, utils/__init__.py:6): force a virtual multi-device
CPU backend for development/test runs on hosts without a TPU slice."""

from __future__ import annotations

import os


def force_cpu_devices(n_devices: int) -> None:
    """Force JAX onto >= ``n_devices`` virtual CPU devices.

    Must be called before the JAX backend initializes: it sets
    ``JAX_PLATFORMS=cpu`` and the ``--xla_force_host_platform_device_count``
    XLA flag, both of which are read once, at backend start-up.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def host_fingerprint() -> str:
    """Short digest of everything that changes XLA's CPU target features
    — the namespace key for :func:`host_cache_dir` and the skew fence in
    AOT executable headers (inference/aot.py). cpuinfo flags alone are
    NOT enough: XLA adds tuning features like +prefer-no-gather/
    +prefer-no-scatter based on microcode-level erratum detection (Intel
    GDS/downfall), so two hosts with identical flag lists can still
    produce incompatible AOT entries (observed round 5: "Target machine
    feature +prefer-no-scatter is not supported on the host machine"
    served from a same-fingerprint cache). Fold in the microcode
    revision, model, and kernel release."""
    import hashlib

    parts = []
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip()
                # x86: flags/microcode/model name; aarch64: Features
                if key in ("flags", "Features", "microcode", "model name"):
                    parts.append(" ".join(sorted(line.split(":", 1)[1].split())))
                    if len(parts) >= 3:
                        break
    except Exception:
        parts.append("nocpuinfo")
    try:
        parts.append(os.uname().release)
    except Exception:
        pass
    return (
        hashlib.sha256("|".join(parts).encode()).hexdigest()[:10]
        if parts
        else "noinfo"
    )


def host_cache_dir(base_dir: str) -> str:
    """Persistent-compile-cache directory namespaced by a host-CPU
    fingerprint.

    XLA:CPU AOT cache entries embed the COMPILE machine's CPU features;
    loading one on a host missing those features only logs a warning
    (cpu_aot_loader.cc: "could lead to execution errors such as SIGILL")
    before executing — observed as nondeterministic mid-run SIGABRTs when a
    shared cache survived a host change between build rounds. Namespacing by
    the feature set makes a moved cache cold instead of lethal."""
    path = os.path.join(base_dir, f"host-{host_fingerprint()}")
    os.makedirs(path, exist_ok=True)
    # Prune only what is provably dead (ADVICE r4: an unconditional prune on
    # a cache volume shared by hosts with different CPU features evicted
    # each other's LIVE caches on every process start, and deleted unrelated
    # user files kept in base_dir): root-level files are removed only when
    # they look like legacy pre-namespacing XLA cache entries; sibling
    # host-* namespaces are NEVER deleted — they are small, and no cheap
    # liveness signal exists (read-only warm hits don't bump mtime).
    try:
        for entry in os.listdir(base_dir):
            full = os.path.join(base_dir, entry)
            if os.path.isfile(full) and entry.startswith(("jit_", "xla_", "cache_")):
                os.unlink(full)
    except OSError:
        pass
    return path
