"""paddle.distributed.rpc (multi-process, TCPStore rendezvous) and
fleet.utils.fs parity tests.
Reference: python/paddle/distributed/rpc/, fleet/utils/fs.py."""
import os
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.utils.fs import (ExecuteError, HDFSClient,
                                                   LocalFS)
from paddle_tpu.testing.child import REPO_ROOT, Child, cpu_env

_RPC_COMPANION = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    from paddle_tpu.distributed import rpc

    def square(x):
        return x * x

    def whoami():
        return rpc.get_worker_info().name

    rank = int(sys.argv[1])
    port = int(sys.argv[2])
    rpc.init_rpc(name=f"worker{{rank}}", rank=rank, world_size=2,
                 master_endpoint=f"127.0.0.1:{{port}}")
    if rank == 1:
        out = rpc.rpc_sync("worker0", square, args=(7,))
        assert out == 49, out
        fut = rpc.rpc_async("worker0", whoami)
        assert fut.wait(timeout=30) == "worker0"
        # exceptions propagate
        try:
            rpc.rpc_sync("worker0", square, args=("a",))
            raise SystemExit("expected TypeError")
        except TypeError:
            pass
        infos = {{w.name for w in rpc.get_all_worker_infos()}}
        assert infos == {{"worker0", "worker1"}}, infos
        agent = rpc._agent[0]
        agent.store.set("client_done", b"1")   # done-signal, not a sleep
        print("RPC_OK")
    else:
        agent = rpc._agent[0]
        deadline = time.time() + 90
        while time.time() < deadline:
            try:
                if agent.store.get("client_done"):
                    break
            except Exception:
                pass
            time.sleep(0.1)
    rpc.shutdown()
""")


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_rpc_two_process_roundtrip(tmp_path):
    script = tmp_path / "rpc_worker.py"
    script.write_text(_RPC_COMPANION.format(repo=REPO_ROOT))
    port = str(_free_port())
    with Child([sys.executable, str(script), "0", port],
               env=cpu_env()) as server, \
            Child([sys.executable, str(script), "1", port],
                  env=cpu_env()) as client:
        client_code = client.wait(120)
        server_code = server.wait(120)
        out0 = server.stdout + server.stderr
        out1 = client.stdout + client.stderr
    assert client_code == 0, f"client failed:\n{out1}\nserver:\n{out0}"
    assert "RPC_OK" in out1
    assert server_code == 0, f"server failed:\n{out0}"


def test_localfs_contract(tmp_path):
    fs = LocalFS()
    root = str(tmp_path / "fsroot")
    fs.mkdirs(os.path.join(root, "sub"))
    fs.touch(os.path.join(root, "a.txt"))
    assert fs.is_exist(root) and fs.is_dir(root)
    assert fs.is_file(os.path.join(root, "a.txt"))
    dirs, files = fs.ls_dir(root)
    assert dirs == ["sub"] and files == ["a.txt"]
    fs.mv(os.path.join(root, "a.txt"), os.path.join(root, "b.txt"))
    assert fs.is_file(os.path.join(root, "b.txt"))
    with pytest.raises(ExecuteError):
        fs.touch(os.path.join(root, "b.txt"), exist_ok=False)
    # upload/download are copies locally
    fs.upload(os.path.join(root, "b.txt"), os.path.join(root, "c.txt"))
    assert fs.is_file(os.path.join(root, "c.txt"))
    fs.delete(root)
    assert not fs.is_exist(root)
    assert fs.ls_dir(root) == ([], [])


def test_hdfs_client_gated():
    with pytest.raises(ExecuteError, match="hadoop"):
        HDFSClient("/nonexistent/hadoop_home")
