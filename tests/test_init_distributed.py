"""Multi-host detection in ``mesh.init_distributed``: decided from a parsed
host list, never from a comma somewhere in an environment variable."""

import jax
import pytest

from gpt_2_distributed_tpu.parallel import mesh
from gpt_2_distributed_tpu.parallel.mesh import init_distributed, tpu_worker_hosts

# What libtpu logs about this very variable when it cannot determine the
# worker set — a sentence with commas, seen verbatim in this sandbox.
LIBTPU_WARNING = (
    "WARNING: could not determine TPU worker hostnames or IP addresses, "
    "please set env var `TPU_WORKER_HOSTNAMES` manually, otherwise libtpu.so "
    "may not properly initialize."
)


@pytest.mark.parametrize("value,hosts", [
    (None, []),
    ("", []),
    ("localhost", ["localhost"]),
    ("t1v-n-0,t1v-n-1", ["t1v-n-0", "t1v-n-1"]),
    ("10.0.0.1, 10.0.0.2 ,10.0.0.3", ["10.0.0.1", "10.0.0.2", "10.0.0.3"]),
    ("h1:8470:10.0.0.1,h2:8470:10.0.0.2",
     ["h1:8470:10.0.0.1", "h2:8470:10.0.0.2"]),
    (LIBTPU_WARNING, []),
    ("host-a,,host-b", []),            # one malformed entry disqualifies all
    ("host-a,not a host", []),
    ("host-a:8470,host-b:8470", []),   # libtpu takes no bare host:port
], ids=["unset", "empty", "one-host", "two-hosts", "three-ips-spaced",
        "triples", "libtpu-warning-sentence", "empty-entry", "spaces",
        "host-port"])
def test_tpu_worker_hosts(value, hosts):
    assert tpu_worker_hosts(value) == hosts


@pytest.fixture()
def no_coordinator_env(monkeypatch):
    for name in ("COORDINATOR_ADDRESS", "MASTER_ADDR", "NUM_PROCESSES",
                 "WORLD_SIZE", "PROCESS_ID", "RANK"):
        monkeypatch.delenv(name, raising=False)
    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda *a, **k: calls.append((a, k)))
    return calls


@pytest.mark.parametrize("value", [LIBTPU_WARNING, "localhost", ""],
                         ids=["warning-sentence", "one-host", "empty"])
def test_single_host_never_rendezvous(no_coordinator_env, monkeypatch, value):
    """A comma in the variable used to mean 'pod': the no-argument
    ``jax.distributed.initialize()`` then waits for peers that do not exist,
    and every CLI hangs before its first step."""
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", value)
    init_distributed()
    assert no_coordinator_env == []


def test_two_hosts_auto_detect(no_coordinator_env, monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t1v-n-0,t1v-n-1")
    init_distributed()
    assert no_coordinator_env == [((), {})]
    assert mesh.tpu_worker_hosts("t1v-n-0,t1v-n-1") == ["t1v-n-0", "t1v-n-1"]
