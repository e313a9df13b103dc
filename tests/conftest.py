"""Test config: run everything on a virtual 8-device CPU mesh.

The reference's distributed tests need real GPUs (SURVEY.md §4); the TPU build tests
sharding on XLA:CPU with `--xla_force_host_platform_device_count=8` for free.
"""
import os

# Must be set before jax initializes (force: the outer env may point at a TPU).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration tests (excluded from "
        "the tier-1 `-m 'not slow'` run)")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


_CAP_PROBE = '''
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=sys.argv[1],
                           num_processes=2, process_id=int(sys.argv[2]))
import numpy as np
from jax.experimental import multihost_utils
out = multihost_utils.process_allgather(np.ones((2,), np.float32))
print("CAP_OK", out.shape, flush=True)
'''


def multiprocess_collectives_supported() -> bool:
    """Backend-capability probe (cached): can THIS jax build run a
    cross-process collective on the CPU backend? Current jaxlib CPU
    clients raise `Multiprocess computations aren't implemented on the
    CPU backend` from the very first allgather, which kept the 2-process
    launch tests permanently red; probing once turns that into an honest
    capability skip while keeping the tests live for backends/builds
    that do support it (TPU pods, newer CPU clients)."""
    import socket
    import subprocess
    import sys

    if "cap" in _mp_cap:
        return _mp_cap["cap"]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        addr = f"127.0.0.1:{s.getsockname()[1]}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen([sys.executable, "-c", _CAP_PROBE, addr,
                               str(r)], env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.DEVNULL, text=True)
             for r in range(2)]
    try:
        outs = [p.communicate(timeout=90)[0] for p in procs]
        ok = all(p.returncode == 0 for p in procs) \
            and all("CAP_OK" in o for o in outs)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        ok = False
    _mp_cap["cap"] = ok
    return ok


_mp_cap: dict = {}


def require_multiprocess_collectives():
    if not multiprocess_collectives_supported():
        pytest.skip("backend capability: jax CPU backend lacks "
                    "multiprocess collectives")
