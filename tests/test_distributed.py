"""Multi-host bring-up: 2-process localhost jax.distributed.

The reference's multi-"node" story is forked processes + shared memory
(``main.py:393-405``); ours is ``jax.distributed`` — every host runs the
same command, ``jax.devices()`` spans the cluster, and collectives ride the
mesh. No multi-host TPU exists here, so this exercises the REAL
``jax.distributed.initialize`` handshake with two local CPU processes
(coordinator on a localhost port), exactly what ``train.py --coordinator
--num-processes --process-id`` wires up.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

# Each child: 2 virtual CPU devices, so the global mesh is 2 procs × 2 = 4.
_CHILD = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, __REPO__)
    from d4pg_tpu.parallel import initialize_distributed, make_mesh

    info = initialize_distributed(
        coordinator_address=__COORD__,
        num_processes=2,
        process_id=int(sys.argv[1]),
    )
    assert info["process_count"] == 2, info
    assert info["local_device_count"] == 2, info
    assert info["global_device_count"] == 4, info
    mesh = make_mesh(dp=4)  # global mesh spans both processes' devices
    assert mesh.shape["dp"] == 4
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    # One real cross-process collective: every process contributes its local
    # shard of a dp-sharded array; the jitted global sum must see all of it.
    arr = jax.make_array_from_callback(
        (4,),
        NamedSharding(mesh, P("dp")),
        lambda idx: jnp.arange(4.0)[idx],
    )
    total = jax.jit(
        jnp.sum, out_shardings=NamedSharding(mesh, P())
    )(arr)
    # fully-addressable replicated output: both processes can read it
    assert float(total) == 6.0, float(total)
    print(f"proc {info['process_index']} OK")
    """
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_localhost_bringup(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "child.py"
    script.write_text(
        _CHILD.replace("__REPO__", repr(repo)).replace("__COORD__", repr(coord))
    )
    env = {
        k: v
        for k, v in os.environ.items()
        # the child script pins its own platform, device count and import
        # path; it must not inherit this harness's
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(rank)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            text=True,
        )
        for rank in (0, 1)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"proc {rank} OK" in out
