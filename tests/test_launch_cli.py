"""paddle_tpu.distributed.launch CLI: env contract, logs, restart
(SURVEY §2.5 Launcher, §5.3 failure detection)."""
import numpy as np

from paddle_tpu.testing.child import run_launch

COMPANION = """
import os, sys
rank = os.environ["PADDLE_TRAINER_ID"]
assert os.environ["PADDLE_TRAINERS_NUM"] == "2"
assert os.environ["PADDLE_MASTER"]
assert os.environ["JAX_PROCESS_ID"] == rank
print("rank", rank, "ok")
marker = sys.argv[1] + "/done." + rank
open(marker, "w").write("1")
"""

FLAKY = """
import os, sys
attempt_file = sys.argv[1] + "/attempts"
n = int(open(attempt_file).read()) if os.path.exists(attempt_file) else 0
open(attempt_file, "w").write(str(n + 1))
sys.exit(0 if n >= 1 else 1)      # fail on first attempt, pass on second
"""


def test_refuses_several_children_on_a_tpu_host(monkeypatch):
    """A chip belongs to one process at a time and the launcher does not
    partition chips: more than one child per TPU host is refused with a
    message that names the supported mode; CPU ranks and the one-process
    default pass."""
    from paddle_tpu.distributed.launch import __main__ as launch
    monkeypatch.setattr(launch, "_local_tpu_present", lambda: True)
    msg = launch._refuse_shared_chips(2, {})
    assert msg and "--nproc_per_node 1" in msg and "JAX_PLATFORMS=cpu" in msg
    assert launch._refuse_shared_chips(1, {}) is None
    assert launch._refuse_shared_chips(2, {"JAX_PLATFORMS": "cpu"}) is None
    monkeypatch.setattr(launch, "_local_tpu_present", lambda: False)
    assert launch._refuse_shared_chips(4, {}) is None


class TestLaunchCLI:
    def test_two_proc_env_contract_and_logs(self, tmp_path):
        r = run_launch(tmp_path, COMPANION, ["--nproc_per_node", "2"],
                        [str(tmp_path)])
        assert r.returncode == 0, r.stderr
        assert (tmp_path / "done.0").exists()
        assert (tmp_path / "done.1").exists()
        # non-zero ranks log to workerlog.N
        assert "ok" in (tmp_path / "log" / "workerlog.1").read_text()

    def test_max_restart_retries_failed_pod(self, tmp_path):
        r = run_launch(tmp_path, FLAKY,
                        ["--nproc_per_node", "1", "--max_restart", "2"],
                        [str(tmp_path)])
        assert r.returncode == 0, r.stderr
        assert (tmp_path / "attempts").read_text() == "2"

    def test_failure_propagates_exit_code(self, tmp_path):
        r = run_launch(tmp_path, "import sys; sys.exit(3)\n",
                        ["--nproc_per_node", "1"], [])
        assert r.returncode == 3


FT_TRAIN = """
# Fault-tolerance companion (SURVEY §5.3): trains a Linear regressor,
# checkpoints every step, dies mid-training on the first attempt, and on
# relaunch resumes from the checkpoint. The loss curve file must end up
# identical to an uninterrupted run.
import os, sys, json
import numpy as np
import paddle_tpu as paddle

workdir = sys.argv[1]
kill_at = int(sys.argv[2])        # <0: never (the uninterrupted oracle run)
steps = 8

paddle.seed(7)
m = paddle.nn.Linear(4, 1)
opt = paddle.optimizer.SGD(0.2, parameters=m.parameters())

ck = os.path.join(workdir, "ck.pdparams")
curve_path = os.path.join(workdir, "curve.jsonl")
start = 0
if os.path.exists(ck):
    state = paddle.load(ck)
    m.set_state_dict(state["model"])
    opt.set_state_dict(state["opt"])
    start = state["step"]

rng = np.random.RandomState(0)
xs = rng.randn(steps, 16, 4).astype(np.float32)
w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)

for step in range(start, steps):
    x = paddle.to_tensor(xs[step])
    y = paddle.to_tensor(xs[step] @ w_true)
    loss = ((m(x) - y) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    with open(curve_path, "a") as f:
        f.write(json.dumps({"step": step,
                            "loss": float(np.asarray(loss._data))}) + "\\n")
    paddle.save({"model": m.state_dict(), "opt": opt.state_dict(),
                 "step": step + 1}, ck)
    if step + 1 == kill_at and not os.path.exists(
            os.path.join(workdir, "died")):
        open(os.path.join(workdir, "died"), "w").write("1")
        os._exit(17)              # simulated worker crash mid-training
"""


class TestFaultToleranceResume:
    def _curve(self, path):
        import json
        rows = [json.loads(l) for l in open(path)]
        # resumed runs re-log nothing before `start`; keep last value per step
        by_step = {}
        for r in rows:
            by_step[r["step"]] = r["loss"]
        return [by_step[i] for i in sorted(by_step)]

    def test_kill_relaunch_resume_matches_uninterrupted(self, tmp_path):
        """Reference contract (launch/controllers/controller.py + elastic):
        a worker dying mid-training is relaunched by --max_restart and the
        checkpoint-resumed loss curve equals the uninterrupted one."""
        int_dir = tmp_path / "interrupted"
        ref_dir = tmp_path / "oracle"
        int_dir.mkdir(), ref_dir.mkdir()

        r = run_launch(tmp_path, FT_TRAIN,
                        ["--nproc_per_node", "1", "--max_restart", "1"],
                        [str(int_dir), "4"])
        assert r.returncode == 0, r.stderr
        assert (int_dir / "died").exists()          # it really crashed
        assert "restarting" in r.stderr             # launcher relaunched it

        r2 = run_launch(tmp_path, FT_TRAIN,
                         ["--nproc_per_node", "1"], [str(ref_dir), "-1"])
        assert r2.returncode == 0, r2.stderr

        got = self._curve(int_dir / "curve.jsonl")
        want = self._curve(ref_dir / "curve.jsonl")
        assert len(got) == len(want) == 8
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_no_restart_budget_fails(self, tmp_path):
        d = tmp_path / "nobudget"
        d.mkdir()
        r = run_launch(tmp_path, FT_TRAIN, ["--nproc_per_node", "1"],
                        [str(d), "2"])
        assert r.returncode == 17                   # crash surfaces


MP_COLLECTIVES = """
# world=2 eager collectives companion: exercises ProcessGroupXLA's
# multi-process path (make_array_from_process_local_data + cached
# shard_map) against hand-computed values — VERDICT r1 weak-8.
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

env = dist.init_parallel_env()
rank, world = env.rank, env.world_size
assert world == 2, world

# all_reduce: sum of (rank+1)*[1,2,3] over 2 ranks
t = paddle.to_tensor(np.array([1., 2., 3.], np.float32) * (rank + 1))
dist.all_reduce(t)
np.testing.assert_allclose(np.asarray(t._data), [3., 6., 9.])

# all_gather
outs = []
dist.all_gather(outs, paddle.to_tensor(
    np.array([float(rank)], np.float32)))
got = sorted(float(np.asarray(o._data)[0]) for o in outs)
assert got == [0.0, 1.0], got

# broadcast from rank 0
b = paddle.to_tensor(np.array([rank * 10.0 + 5.0], np.float32))
dist.broadcast(b, src=0)
np.testing.assert_allclose(np.asarray(b._data), [5.0])

# reduce to dst=1: only dst must hold the sum
r = paddle.to_tensor(np.array([float(rank + 1)], np.float32))
dist.reduce(r, dst=1)
expect = 3.0 if rank == 1 else float(rank + 1)
np.testing.assert_allclose(np.asarray(r._data), [expect])

# reduce_scatter: each rank holds [r+1, r+2]; sums [3, 5]; rank r gets [3+2r]
rs_out = paddle.to_tensor(np.zeros((1,), np.float32))
rs_in = [paddle.to_tensor(np.array([rank + 1.0], np.float32)),
         paddle.to_tensor(np.array([rank + 2.0], np.float32))]
dist.reduce_scatter(rs_out, rs_in)
np.testing.assert_allclose(np.asarray(rs_out._data).reshape(-1),
                           [3.0 + 2.0 * rank])

# alltoall: rank r sends [r*10+0, r*10+1] -> rank r receives [r, 10+r]
a2a_out = []
dist.alltoall([paddle.to_tensor(np.array([rank * 10.0], np.float32)),
               paddle.to_tensor(np.array([rank * 10.0 + 1.0], np.float32))],
              a2a_out)
got2 = [float(np.asarray(t._data).reshape(-1)[0]) for t in a2a_out]
assert got2 == [0.0 + rank, 10.0 + rank], got2

open(sys.argv[1] + f"/ok.{rank}", "w").write("1")
print("rank", rank, "collectives ok")
"""


class TestMultiProcessCollectives:
    def test_world2_eager_collectives(self, tmp_path):
        r = run_launch(tmp_path, MP_COLLECTIVES,
                        ["--nproc_per_node", "2"], [str(tmp_path)])
        assert r.returncode == 0, (r.stdout, r.stderr)
        assert (tmp_path / "ok.0").exists() and (tmp_path / "ok.1").exists()


ELASTIC_WORKER = """
# Elastic end-to-end companion: each worker registers + heartbeats; rank 0
# watches membership. Worker 1 exits mid-run -> rank 0 must observe RESTART
# (scale-down) within the timeout. SURVEY §5.3 / VERDICT r1 elastic gap.
import os, sys, time
from paddle_tpu.distributed.fleet.elastic.manager import (ElasticManager,
                                                          ElasticStatus)
workdir = sys.argv[1]
rank = os.environ["PADDLE_TRAINER_ID"]
os.environ["PADDLE_ELASTIC_ENABLE"] = "1"
os.environ["PADDLE_ELASTIC_NP"] = "1:2"
os.environ["PADDLE_ELASTIC_SERVER"] = os.environ["PADDLE_MASTER"].rsplit(
    ":", 1)[0] + ":" + str(int(os.environ["PADDLE_MASTER"].rsplit(
        ":", 1)[1]) + 37)

mgr = ElasticManager(heartbeat_interval=0.2)
mgr.register()
if rank == "1":
    time.sleep(2.0)
    mgr.exit(completed=False)      # stop heartbeating: simulated departure
    open(workdir + "/left.1", "w").write("1")
    sys.exit(0)

# rank 0: wait until both workers seen, then watch for the departure
deadline = time.time() + 30
st = None
saw_two = False
while time.time() < deadline:
    alive = mgr.alive_workers(timeout=1.5)
    if len(alive) == 2:
        saw_two = True
    st = mgr.watch()
    # only the DOWN transition counts: both workers must have been seen
    # and the restart must coincide with the shrunken membership
    if saw_two and st == ElasticStatus.RESTART and len(alive) == 1:
        open(workdir + "/restart.0", "w").write("1")
        break
    time.sleep(0.3)
mgr.exit()
assert os.path.exists(workdir + "/restart.0"), (saw_two, st)
print("elastic scale-down observed")
"""


class TestElasticEndToEnd:
    def test_scale_down_triggers_restart(self, tmp_path):
        r = run_launch(tmp_path, ELASTIC_WORKER,
                        ["--nproc_per_node", "2"], [str(tmp_path)])
        assert r.returncode == 0, (r.stdout, r.stderr)
        assert (tmp_path / "left.1").exists()
        assert (tmp_path / "restart.0").exists()


DP4_TRAIN = """
# world=4 multi-host-shaped companion (VERDICT r2 #7): collectives at
# world=4, a data-parallel train loop over per-rank shards with grad
# all-reduce, a mid-training pod crash (rank 2 dies once), launcher
# restart, checkpoint-resume — final params must equal the uninterrupted
# full-batch oracle (computed by the test process).
import os, sys, json
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

workdir = sys.argv[1]
kill_at = int(sys.argv[2])
env = dist.init_parallel_env()
rank, world = env.rank, env.world_size
assert world == 4, world

# -- collectives at world=4, hand-computed oracles --
t = paddle.to_tensor(np.array([1.0, 2.0], np.float32) * (rank + 1))
dist.all_reduce(t)                       # sum over ranks: (1+2+3+4)=10
np.testing.assert_allclose(np.asarray(t._data), [10.0, 20.0])
outs = []
dist.all_gather(outs, paddle.to_tensor(np.array([float(rank)], np.float32)))
assert sorted(float(np.asarray(o._data)[0]) for o in outs) == [0., 1., 2., 3.]

# -- DP training with checkpoint-resume across a pod restart --
steps, per_rank = 6, 4
paddle.seed(3)
m = paddle.nn.Linear(4, 1)
opt = paddle.optimizer.SGD(0.2, parameters=m.parameters())

ck = os.path.join(workdir, "ck.pdparams")
start = 0
if os.path.exists(ck):
    state = paddle.load(ck)
    m.set_state_dict(state["model"])
    opt.set_state_dict(state["opt"])
    start = state["step"]

rng = np.random.RandomState(0)
xs = rng.randn(steps, world * per_rank, 4).astype(np.float32)
w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)

for step in range(start, steps):
    sl = slice(rank * per_rank, (rank + 1) * per_rank)
    x = paddle.to_tensor(xs[step, sl])
    y = paddle.to_tensor(xs[step, sl] @ w_true)
    loss = ((m(x) - y) ** 2).mean()
    loss.backward()
    for p in m.parameters():             # DP grad averaging over the world
        dist.all_reduce(p.grad)
        p.grad._data = p.grad._data / world
    opt.step()
    opt.clear_grad()
    if rank == 0:
        paddle.save({"model": m.state_dict(), "opt": opt.state_dict(),
                     "step": step + 1}, ck)
    dist.barrier()
    if rank == 2 and step + 1 == kill_at and not os.path.exists(
            os.path.join(workdir, "died")):
        open(os.path.join(workdir, "died"), "w").write("1")
        os._exit(19)                     # simulated worker crash

if rank == 0:
    w = np.asarray(m.parameters()[0]._data)
    np.save(os.path.join(workdir, "final_w.npy"), w)
open(os.path.join(workdir, f"ok.{rank}"), "w").write("1")
print("rank", rank, "dp4 done")
"""


class TestWorld4LaunchTrainResume:
    def test_nprocs4_collectives_dp_train_crash_resume(self, tmp_path):
        """The multi-host-shaped proof at world=4: launch 4 ranks via the
        CLI, run collectives + a DP train loop, crash one rank mid-run,
        let --max_restart relaunch the pod, resume from the checkpoint,
        and match the single-process full-batch oracle exactly."""
        d = tmp_path / "dp4"
        d.mkdir()
        r = run_launch(tmp_path, DP4_TRAIN,
                        ["--nproc_per_node", "4", "--max_restart", "1"],
                        [str(d), "3"])
        assert r.returncode == 0, (r.stdout, r.stderr)
        assert (d / "died").exists()                # really crashed
        for i in range(4):
            assert (d / f"ok.{i}").exists()

        # single-process full-batch oracle (same seed/init/schedule)
        import paddle_tpu as paddle
        steps, world, per_rank = 6, 4, 4
        paddle.seed(3)
        m = paddle.nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(0.2, parameters=m.parameters())
        rng = np.random.RandomState(0)
        xs = rng.randn(steps, world * per_rank, 4).astype(np.float32)
        w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
        for step in range(steps):
            x = paddle.to_tensor(xs[step])
            y = paddle.to_tensor(xs[step] @ w_true)
            loss = ((m(x) - y) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
        want = np.asarray(m.parameters()[0]._data)
        got = np.load(d / "final_w.npy")
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


ELASTIC4_WORKER = """
# 4-worker elastic companion: rank 3 departs mid-run; rank 0 must observe
# the scale-down (RESTART with 3 alive) within the timeout.
import os, sys, time
from paddle_tpu.distributed.fleet.elastic.manager import (ElasticManager,
                                                          ElasticStatus)
workdir = sys.argv[1]
rank = os.environ["PADDLE_TRAINER_ID"]
os.environ["PADDLE_ELASTIC_ENABLE"] = "1"
os.environ["PADDLE_ELASTIC_NP"] = "1:4"
os.environ["PADDLE_ELASTIC_SERVER"] = os.environ["PADDLE_MASTER"].rsplit(
    ":", 1)[0] + ":" + str(int(os.environ["PADDLE_MASTER"].rsplit(
        ":", 1)[1]) + 41)

mgr = ElasticManager(heartbeat_interval=0.2)
mgr.register()
if rank == "3":
    # leave only AFTER full membership was observable, else rank 0 may
    # never see 4 alive and the scale-down transition is unprovable
    deadline = time.time() + 25
    while time.time() < deadline:
        if len(mgr.alive_workers(timeout=1.5)) == 4:
            break
        time.sleep(0.2)
    time.sleep(1.0)                    # let rank 0 observe 4-alive too
    mgr.exit(completed=False)
    open(workdir + "/left.3", "w").write("1")
    sys.exit(0)
if rank != "0":
    # keep heartbeating at least as long as rank 0's 30 s watch window —
    # exiting earlier would drop alive below 3 and make the scale-down
    # condition unsatisfiable on a slow machine
    deadline = time.time() + 35
    while time.time() < deadline and not os.path.exists(
            workdir + "/restart.0"):
        time.sleep(0.3)
    mgr.exit()
    sys.exit(0)

deadline = time.time() + 30
saw_four = False
while time.time() < deadline:
    alive = mgr.alive_workers(timeout=1.5)
    if len(alive) == 4:
        saw_four = True
    st = mgr.watch()
    if saw_four and st == ElasticStatus.RESTART and len(alive) == 3:
        open(workdir + "/restart.0", "w").write("1")
        break
    time.sleep(0.3)
mgr.exit()
assert os.path.exists(workdir + "/restart.0")
print("elastic 4-worker scale-down observed")
"""


class TestElastic4:
    def test_four_worker_scale_down(self, tmp_path):
        r = run_launch(tmp_path, ELASTIC4_WORKER,
                        ["--nproc_per_node", "4"], [str(tmp_path)])
        assert r.returncode == 0, (r.stdout, r.stderr)
        assert (tmp_path / "left.3").exists()
        assert (tmp_path / "restart.0").exists()
