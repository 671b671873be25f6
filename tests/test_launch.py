"""Real two-process multi-host smoke through the launcher (reference
strategy: test/collective/test_communication_api_base.py spawning worker
processes; launch/controllers/master.py:73 rendezvous) + elastic
membership over the cross-process FileStore (fleet/elastic/manager.py)."""
import os
import socket
import subprocess
import sys
import threading
import time

import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.skipif(
    __import__("jax").__version_info__ < (0, 5),
    reason="cross-process collectives on the CPU backend are "
           "unimplemented in this jaxlib (XLA: 'Multiprocess "
           "computations aren't implemented on the CPU backend')")
def test_two_process_psum_and_sharded_checkpoint(tmp_path):
    from paddle_tpu.parallel.launch.main import launch

    worker = os.path.join(os.path.dirname(__file__), "launch_worker.py")
    master = f"127.0.0.1:{_free_port()}"
    # the workers must not inherit the 8-device forcing of this test
    # process: each side of the 2-process world runs 1 CPU device
    saved = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    try:
        rc = launch(["--nproc_per_node", "2", "--master", master,
                     "--max_restart", "0", "--log_dir",
                     str(tmp_path / "logs"), worker, str(tmp_path)])
    finally:
        if saved is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = saved
    logs = ""
    logdir = tmp_path / "logs"
    if logdir.exists():
        for f in sorted(logdir.iterdir()):
            logs += f"\n--- {f.name} ---\n" + f.read_text()[-2000:]
    assert rc == 0, f"launcher failed rc={rc}\n{logs}"
    for rank in range(2):
        assert (tmp_path / f"psum_ok.{rank}").exists(), \
            f"rank {rank} psum marker missing\n{logs}"
        assert (tmp_path / f"ckpt_ok.{rank}").exists(), \
            f"rank {rank} checkpoint marker missing\n{logs}"
        assert (tmp_path / f"moe_ok.{rank}").exists(), \
            f"rank {rank} MoE global_scatter/gather marker missing\n{logs}"
    # both ranks' shard files and metadata exist
    assert (tmp_path / "ckpt" / "0.npz").exists()
    assert (tmp_path / "ckpt" / "1.npz").exists()
    assert (tmp_path / "ckpt" / "meta.0.json").exists()
    assert (tmp_path / "ckpt" / "meta.1.json").exists()


def test_checkpoint_resave_smaller_world_ignores_stale_metas(tmp_path):
    """A re-save into the same directory must not merge leftover
    higher-rank metas from an earlier, larger world (elastic resume)."""
    import json

    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel.checkpoint import (load_state_dict,
                                                save_state_dict)

    path = str(tmp_path / "ckpt")
    good = np.arange(8, dtype=np.float32).reshape(2, 4)
    save_state_dict({"w": jnp.asarray(good)}, path)
    # forge a stale rank-1 meta from a previous 2-process save pointing at
    # garbage data
    np.savez(os.path.join(path, "1.npz"),
             **{"w::0": np.full((2, 4), 99.0, np.float32)})
    with open(os.path.join(path, "meta.1.json"), "w") as f:
        json.dump({"world": 2, "entries": {"w": {
            "shape": [2, 4], "dtype": "float32",
            "chunks": [{"offset": [0, 0], "shape": [2, 4],
                        "file": "1.npz", "key": "w::0"}]}}}, f)
    state = {"w": jnp.zeros((2, 4), jnp.float32)}
    load_state_dict(state, path)
    np.testing.assert_array_equal(np.asarray(state["w"]), good)


class TestFileStore:
    def test_cross_process_put_get(self, tmp_path):
        from paddle_tpu.parallel.elastic import FileStore

        store = FileStore(str(tmp_path))
        code = ("import sys; sys.path.insert(0, %r); "
                "from paddle_tpu.parallel.elastic import FileStore; "
                "FileStore(%r).put('/job/nodes/b', 'alive')" % (
                    os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))), str(tmp_path)))
        subprocess.run([sys.executable, "-c", code], check=True)
        assert store.get("/job/nodes/b") == "alive"
        assert store.prefix("/job/nodes/") == {"/job/nodes/b": "alive"}

    def test_ttl_expiry(self, tmp_path):
        from paddle_tpu.parallel.elastic import FileStore

        store = FileStore(str(tmp_path))
        store.put("k", "v", ttl=0.2)
        assert store.get("k") == "v"
        time.sleep(0.3)
        assert store.get("k") is None
        assert store.prefix("") == {}

    def test_elastic_rerank_scale_up_down(self, tmp_path):
        from paddle_tpu.parallel.elastic import ElasticManager, FileStore

        store_dir = str(tmp_path)
        a = ElasticManager(FileStore(store_dir), host="node-a",
                           np_range=(1, 3), heartbeat_ttl=1.0).register()
        a.watch(poll_interval=0.05)
        b = ElasticManager(FileStore(store_dir), host="node-b",
                           np_range=(1, 3), heartbeat_ttl=1.0).register()
        deadline = time.time() + 5
        while not a.need_restart and time.time() < deadline:
            time.sleep(0.05)
        assert a.need_restart, "scale-up not observed"
        assert a.members() == ["node-a", "node-b"]
        assert a.rank_of() == 0 and a.rank_of("node-b") == 1
        a.need_restart = False
        b.exit()  # explicit deregistration (scale-down)
        deadline = time.time() + 5
        while not a.need_restart and time.time() < deadline:
            time.sleep(0.05)
        assert a.need_restart, "scale-down not observed"
        assert a.members() == ["node-a"]
        a.exit()


def test_launcher_elastic_rescale(tmp_path):
    """Membership change must make the supervisor re-rank and respawn the
    workers with the new world size (reference: elastic manager watch ->
    kill -> relaunch, manager.py:247,308)."""
    from paddle_tpu.parallel.elastic import ElasticManager, FileStore
    from paddle_tpu.parallel.launch.main import launch

    store = tmp_path / "store"
    out = tmp_path / "out"
    out.mkdir()
    worker = tmp_path / "worker.py"
    worker.write_text(
        "import os, sys, time, uuid\n"
        "out = sys.argv[1]\n"
        "n = os.environ['PADDLE_TRAINERS_NUM']\n"
        "open(os.path.join(out, f'mark.{n}.{uuid.uuid4().hex}'), 'w')"
        ".write('x')\n"
        "end = time.time() + 180\n"
        "while time.time() < end:\n"
        "    if os.path.exists(os.path.join(out, 'stop')):\n"
        "        break\n"
        "    time.sleep(0.05)\n")

    rc_box = {}

    def run():
        rc_box["rc"] = launch(
            ["--nproc_per_node", "1", "--nnodes", "1:2",
             "--elastic_store", str(store), "--host_id", "node-a",
             "--max_restart", "0", str(worker), str(out)])

    t = threading.Thread(target=run, daemon=True)
    t.start()

    # ONE deadline for the whole exchange, generous enough for a machine
    # that five other test workers keep busy: a spawn, a kill and the
    # supervisor's settle window each stretch there
    deadline = time.time() + 120

    def wait_marks(world, count):
        while time.time() < deadline:
            n = len([f for f in out.iterdir()
                     if f.name.startswith(f"mark.{world}.")])
            if n >= count:
                return True
            time.sleep(0.1)
        return False

    assert wait_marks(1, 1), "initial world-1 worker never started"
    # default heartbeat TTL: node-b's heartbeat thread lives in this
    # (busy) process, and it leaves by exit(), not by expiry
    b = ElasticManager(FileStore(str(store)), host="node-b",
                       np_range=(1, 2)).register()
    assert wait_marks(2, 1), "scale-up respawn (world 2) not observed"
    b.exit()
    assert wait_marks(1, 2), "scale-down respawn (world 1) not observed"
    (out / "stop").touch()
    t.join(timeout=max(20.0, deadline - time.time()))
    assert not t.is_alive(), "launcher did not exit after workers stopped"
    assert rc_box.get("rc") == 0
