"""Virtual-mesh provisioning: force JAX onto an n-device CPU backend.

Multi-chip sharding paths (jepsen_tpu.parallel) are developed and CI-tested
without TPU hardware by running XLA's host platform with n virtual devices.
The knobs are only read at jax's *first* import, so provisioning means
setting the platform + device-count env vars before that import and, in
process, pinning the platform through ``jax.config`` too.

This module must stay import-light (os only): callers import it *before*
jax is ever imported.
"""
import os


def virtual_cpu_env(n_devices: int, env=None):
    """Make ``env`` (default: a copy of os.environ) provision ``n_devices``
    virtual CPU devices for a *fresh* interpreter. Mutates and returns it.
    """
    env = dict(os.environ) if env is None else env
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env.setdefault("JAX_ENABLE_X64", "0")
    return env


def provision_in_process(n_devices: int = 8) -> None:
    """Provision the *current* process: call before jax is imported
    anywhere, e.g. from a test conftest. Also pins the platform through
    jax.config."""
    virtual_cpu_env(n_devices, env=os.environ)
    import jax

    jax.config.update("jax_platforms", "cpu")


def device_identity() -> dict:
    """What this process checks on, as JAX reports it — recorded next
    to every figure so a CPU number never reads as a chip number."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
