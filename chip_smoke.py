#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user would
call, at the full width AND depth of the flagship (12 layers x 768,
6 heads x 128, MLP 3072, vocab 32000; weights from a seed):

1. coord server, then ``python -m edl_tpu.collective.launch ...
   examples/lm/train_lm.py`` for two epochs with every elastic mechanism
   the launcher switches on (step ledger, memstate tee, delta
   replication, preempt check, hang watchdog), then a second launch on
   the same checkpoint directory that restores on the chip and steps on;
2. ``python -m edl_tpu.serving.replica --checkpoint_dir <that one>``,
   ``python -m edl_tpu.gateway`` and a handful of ``gate_generate``
   calls: short prompts, a chunked prefill, a prefix hit, all greedy and
   checked against ``models.generate`` run from the same checkpoint;
   then SIGTERM, and the replica must drain and exit 0.

On a four-chip host it also trains on a dp=4 and a dp=2 x tp=2 mesh
against a one-chip run (``--devices 0``) at the same global batch, and
serves with ``--tp 2``.  (Two launchers sharing the host's chips are
refused by the launcher: ROADMAP S9c.)

This process never initialises a JAX backend (it never imports jax): a
chip belongs to one process, so every chip-holding step is a child, one
at a time, and anything this script needs from the device it reads from
a child's output, its log, or the coord store.  Any phase that fails,
times out or finds ``cpu`` ends the run non-zero with the tail of the
child's log.  The last line of stdout is the one JSON result.
"""

from __future__ import annotations

import ast
import contextlib
import json
import os
import re
import resource
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

from edl_tpu.cluster.recovery import load_recovery_records  # noqa: E402
from edl_tpu.cluster.status import Status, load_job_status  # noqa: E402
from edl_tpu.coord.client import connect_wait  # noqa: E402
from edl_tpu.memstate.advert import read_committed_step  # noqa: E402
from edl_tpu.rpc.client import RpcClient  # noqa: E402
from edl_tpu.utils.network import find_free_port  # noqa: E402

DIMS = {"layers": 12, "embed": 768, "heads": 6, "mlp": 3072,
        "vocab": 32000}
SEQ_LEN = 1024
# 12 steps an epoch: the epoch-end commit anchors a delta chain at step
# 12 and the step-20 record (EDL_TPU_DELTA_EVERY=10) opens it
STEPS = 12
# doc/serving.md "Sizing a replica for one chip" has the arithmetic
SLOTS, MAX_LEN = 16, 2048
MAX_NEW = 8
# dp=4 / dp=2 x tp=2 against one chip at the same global batch, same
# data and seed: only reduction order differs, so the held-out NLL
# (about ln 32000 = 10.4 here) must agree to bf16's three digits
MESH_NLL_TOL = 0.05


def model_args() -> list[str]:
    return [a for k, v in DIMS.items() for a in (f"--{k}", str(v))]


class Smoke:
    def __init__(self) -> None:
        base = os.path.join(ROOT, "chiprun_out", "chip_smoke")
        os.makedirs(base, exist_ok=True)
        n = sum(d.startswith("run-") for d in os.listdir(base))
        self.logs = os.path.join(base, f"run-{n}")
        os.makedirs(self.logs)
        self.data = tempfile.mkdtemp(prefix="chip_smoke-")
        self.children: list[subprocess.Popen] = []
        self.times: dict[str, float] = {}
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = ROOT + os.pathsep + self.env.get(
            "PYTHONPATH", "")
        self.coord = ""
        self.store = None

    # -- children --------------------------------------------------------
    def spawn(self, name: str, argv: list[str],
              env: dict[str, str] | None = None) -> subprocess.Popen:
        log = os.path.join(self.logs, f"{name}.log")
        with open(log, "ab") as f:
            proc = subprocess.Popen(
                [sys.executable, "-u", *argv], cwd=ROOT, stdout=f,
                stderr=subprocess.STDOUT, env={**self.env, **(env or {})},
                start_new_session=True)
        proc.log = log  # noqa: SLF001 — where fail() finds the tail
        self.children.append(proc)
        return proc

    def fail(self, msg: str, *procs: subprocess.Popen) -> None:
        for p in procs:
            msg += f"\n--- tail of {p.log} ---\n{tail(p.log)}"
        raise SystemExit(f"chip_smoke FAILED: {msg}")

    def wait_exit(self, proc: subprocess.Popen, timeout: float,
                  what: str) -> None:
        try:
            rc = proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.fail(f"{what} still running after {timeout:.0f}s", proc)
        if rc != 0:
            self.fail(f"{what} exited {rc}", proc)

    def wait_line(self, proc: subprocess.Popen, pattern: str,
                  timeout: float, what: str) -> re.Match:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = re.search(pattern, read(proc.log))
            if m:
                return m
            if proc.poll() is not None:
                self.fail(f"{what} exited {proc.returncode} before "
                          f"/{pattern}/", proc)
            time.sleep(0.2)
        self.fail(f"{what}: no /{pattern}/ within {timeout:.0f}s", proc)

    def stop_all(self) -> None:
        for sig in (signal.SIGTERM, signal.SIGKILL):
            for p in self.children:
                if p.poll() is None:
                    try:
                        os.killpg(p.pid, sig)
                    except ProcessLookupError:
                        pass
            deadline = time.monotonic() + 10
            for p in self.children:
                try:
                    p.wait(max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    pass

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase; a phase that raises records nothing."""
        t0 = time.monotonic()
        print(f"[chip_smoke] {name} ...", flush=True)
        yield
        self.times[name] = round(time.monotonic() - t0, 1)
        print(f"[chip_smoke] {name} ok in {self.times[name]}s", flush=True)

    # -- phases ----------------------------------------------------------
    def probe(self) -> dict:
        """Ask a child what JAX sees; the child exits (and frees the
        chip) before anything else starts."""
        code = ("import json, jax; d = jax.devices(); print(json.dumps("
                "{'platform': d[0].platform, 'kind': d[0].device_kind, "
                "'count': len(d)}))")
        proc = self.spawn("probe", ["-c", code])
        self.wait_exit(proc, 180, "device probe")
        device = json.loads(read(proc.log).strip().splitlines()[-1])
        if device["platform"] == "cpu":
            self.fail(f"JAX found no accelerator: {device}")
        return device

    def start_coord(self) -> None:
        port = find_free_port()
        self.coord = f"127.0.0.1:{port}"
        self.spawn("coord", ["-m", "edl_tpu.coord.server", "--host",
                             "127.0.0.1", "--port", str(port)])
        self.store = connect_wait(self.coord)

    def train(self, name: str, ckpt: str, epochs: int, device: dict,
              extra: tuple[str, ...] = (), launch: tuple[str, ...] = (),
              timeout: float = 900) -> dict:
        """One launcher run to SUCCEED; returns what its trainer logged."""
        job = f"smoke-{name}"
        proc = self.spawn(name, [
            "-m", "edl_tpu.collective.launch", "--job_id", job,
            "--coord_endpoints", self.coord, "--nodes_range", "1:1",
            "--checkpoint_dir", ckpt,
            "--log_dir", os.path.join(self.logs, f"{name}-workers"),
            *launch, "examples/lm/train_lm.py", "--", *model_args(),
            "--seq_len", str(SEQ_LEN), "--fused_ce",
            "--steps_per_epoch", str(STEPS), "--epochs", str(epochs),
            *extra])
        self.wait_exit(proc, timeout, f"launcher {name}")
        if load_job_status(self.store, job) != Status.SUCCEED:
            self.fail(f"job {job} is not SUCCEED in the coord store", proc)
        return self.check_trainer_log(proc, job, device, epochs)

    def check_trainer_log(self, proc, job: str, device: dict,
                          epochs: int) -> dict:
        log = read(proc.log)
        m = re.search(r"devices: platform=(\w+) device_kind=(.+?) "
                      r"count=(\d+) mesh=(\{.*?\})", log)
        if not m:
            self.fail("trainer logged no device line", proc)
        if m.group(1) != device["platform"] or m.group(2) != device["kind"]:
            self.fail(f"trainer ran on {m.group(1)}/{m.group(2)}, the "
                      f"probe saw {device}", proc)
        out = {"device_count": int(m.group(3)), "mesh": m.group(4)}
        m = re.search(r"\[train_lm\] rank=0/1 .* dtype=(\w+) remat=(\w+) "
                      r"resume_epoch=(\d+)", log)
        if not m:
            self.fail("no [train_lm] start line", proc)
        if m.group(1) != "bfloat16":
            self.fail(f"trained in {m.group(1)}, not bfloat16", proc)
        out["remat"], out["resume_epoch"] = m.group(2), int(m.group(3))
        # auto must have resolved to splash at the training shape
        if not re.search(rf"attention auto: L={SEQ_LEN}/{SEQ_LEN} D=128 "
                         r"-> splash", log):
            self.fail("attention auto did not pick splash at "
                      f"L={SEQ_LEN} D=128", proc)
        if re.search(rf"L={SEQ_LEN}/{SEQ_LEN} D=128 not tileable", log):
            self.fail("attention fell back to dense", proc)
        nll = {int(e): float(v) for e, v in re.findall(
            r"\[train_lm\] epoch (\d+): val_nll=([0-9.naninf-]+)", log)}
        want = list(range(out["resume_epoch"], epochs))
        if sorted(nll) != want or not all(0 < v < 20 for v in nll.values()):
            self.fail(f"val_nll per epoch {nll}, wanted finite values for "
                      f"epochs {want}", proc)
        out["val_nll"] = nll
        # the elastic machinery the launcher switches on must have
        # ENGAGED: trainer.py logs and carries on when the tee or the
        # replicator cannot be built, which would be a different program
        for dead in ("memstate tee unavailable", "delta replicator "
                     "unavailable", "memstate tee op", "delta replicator op",
                     "delta: push of seq", "rejected seq"):
            if dead in log:
                self.fail(f"elastic machinery broke: {dead!r}", proc)
        last = epochs * STEPS
        if f"memstate: staged step {last} " not in log:
            self.fail(f"memstate tee never staged step {last}", proc)
        if read_committed_step(self.store, job) != last:
            self.fail(f"memstate committed-step record is "
                      f"{read_committed_step(self.store, job)}, not {last}",
                      proc)
        if not re.search(r"delta: chain on base step \d+ opened at step "
                         r"\d+ \([1-9]\d* changed shards", log):
            self.fail("delta replicator sealed no record", proc)
        mem = re.search(r"device memory after step .*?: (\{.*\})", log)
        out["memory_mib"] = mem.group(1) if mem else None
        return out

    def restore_source(self, job: str) -> str:
        halves = load_recovery_records(self.store, job)
        sources = {t.get("restore_source") for h in halves.values()
                   for t in h.get("trainer", {}).values()}
        if len(sources) != 1 or None in sources:
            self.fail(f"recovery records of {job} name restore sources "
                      f"{sources}: {halves}")
        return sources.pop()

    def refuse_oversize(self, ckpt: str) -> str:
        """64 slots x 2048 tokens in f32 wants 27 GiB of KV: the engine
        must say so at construction, with the sizes, not die in XLA on
        a request."""
        proc = self.spawn("oversize-replica", [
            "-m", "edl_tpu.serving.replica", "--coord_endpoints", self.coord,
            "--job_id", "smoke-oversize", "--host", "127.0.0.1",
            "--checkpoint_dir", ckpt, *model_args(),
            "--max_len", str(MAX_LEN), "--slots", "64"])
        try:
            rc = proc.wait(300)
        except subprocess.TimeoutExpired:
            self.fail("the oversize replica neither started nor refused",
                      proc)
        m = re.search(r"ValueError: (engine does not fit .* slot slabs "
                      r".* block pool .* GiB limit.*)", read(proc.log))
        if rc == 0 or not m:
            self.fail(f"the oversize replica exited {rc} without the "
                      f"sizing message", proc)
        return m.group(1)

    def serve(self, name: str, ckpt: str, device: dict,
              extra: tuple[str, ...] = ()) -> dict:
        """replica + gateway, the request mix, SIGTERM drain."""
        job = f"smoke-{name}"
        replica = self.spawn(f"{name}-replica", [
            "-m", "edl_tpu.serving.replica", "--coord_endpoints", self.coord,
            "--job_id", job, "--replica_id", "r0", "--host", "127.0.0.1",
            "--checkpoint_dir", ckpt, *model_args(), "--max_len", str(MAX_LEN),
            "--slots", str(SLOTS), *extra])
        m = self.wait_line(replica, r"\[edl-replica\] r0 serving on (\S+)",
                           600, "replica")
        replica_ep = m.group(1)
        log = read(replica.log)
        m = re.search(r"devices: platform=(\w+) device_kind=(.+?) "
                      r"count=(\d+)", log)
        if not m or m.group(1) != device["platform"]:
            self.fail(f"replica is not on {device['platform']}", replica)
        gateway = self.spawn(f"{name}-gateway", [
            "-m", "edl_tpu.gateway", "--coord_endpoints", self.coord,
            "--job_id", job, "--host", "127.0.0.1"])
        m = self.wait_line(gateway, r"\[edl-gateway\] serving on (\S+)",
                           60, "gateway")
        gw = RpcClient(m.group(1), 600)

        def ask(prompt: list[int]) -> list[int]:
            if replica.poll() is not None or gateway.poll() is not None:
                self.fail("a serving child died mid-phase", replica, gateway)
            toks = gw.call("gate_generate", prompt=prompt, max_new=MAX_NEW,
                           timeout=600)["tokens"]
            if len(toks) != MAX_NEW or not all(
                    0 <= t < DIMS["vocab"] for t in toks):
                self.fail(f"bad answer {toks} for a {len(prompt)}-token "
                          f"prompt", replica, gateway)
            return toks

        prompts = {
            "short": seeded_prompt(1, 9),
            "blocks": seeded_prompt(2, 40),     # two full 16-token blocks
            "long": seeded_prompt(3, 700),      # > EDL_TPU_PREFILL_CHUNK
        }
        answers = {k: ask(p) for k, p in prompts.items()}
        again = ask(prompts["blocks"])          # now a prefix hit
        if again != answers["blocks"]:
            self.fail(f"greedy answers differ across a prefix hit: "
                      f"{answers['blocks']} then {again}", replica)
        gw.close()
        with RpcClient(replica_ep, 60) as rc:
            stats = rc.call("serve_stats")["engine"]
        want = {"kv_block": 16, "requests_done": 4}
        for key, val in want.items():
            if stats.get(key) != val:
                self.fail(f"engine stats {key}={stats.get(key)}, wanted "
                          f"{val}: {stats}", replica)
        for key in ("kv_prefix_hits", "chunked_admissions",
                    "prefill_chunks", "kv_prefill_tokens_skipped"):
            if not stats.get(key, 0) >= 1:
                self.fail(f"engine stats {key}={stats.get(key)}: the "
                          f"path never ran: {stats}", replica)
        os.kill(replica.pid, signal.SIGTERM)
        self.wait_exit(replica, 120, "replica after SIGTERM (drain)")
        os.killpg(gateway.pid, signal.SIGTERM)
        gateway.wait(30)
        return {"prompts": prompts, "answers": answers, "stats": stats,
                "kv_log": re.search(r"kv cache: .*", read(replica.log))
                .group(0)}

    def reference(self, ckpt: str, served: dict) -> None:
        """``models.generate`` (the plain decode loop the engine's parity
        tests compare against) from the same checkpoint, in a child that
        takes the chip after the replica has gone."""
        job = os.path.join(self.logs, "reference.json")
        with open(job, "w") as f:
            json.dump({"ckpt": ckpt, "max_len": MAX_LEN, "max_new": MAX_NEW,
                       "dims": DIMS, "prompts": served["prompts"]}, f)
        proc = self.spawn("reference", ["-c", _REFERENCE, job])
        self.wait_exit(proc, 600, "reference generate")
        ref = json.loads(read(proc.log).strip().splitlines()[-1])
        for key, toks in served["answers"].items():
            if toks != ref[key]:
                self.fail(f"served tokens for {key!r} {toks} != "
                          f"generate() {ref[key]}", proc)


# argv[1]: a json file {ckpt, dims, max_len, max_new, prompts}; prints
# {name: tokens}.  The restore mirrors serving/replica.py:main.
_REFERENCE = """
import json, sys
import jax, jax.numpy as jnp, optax
from edl_tpu.models.generate import generate
from edl_tpu.models.transformer import TransformerConfig, TransformerLM
from edl_tpu.train.checkpoint import CheckpointManager
from edl_tpu.train.state import TrainState
from edl_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache()
job = json.load(open(sys.argv[1]))
d = job["dims"]
cfg = TransformerConfig(vocab_size=d["vocab"], num_layers=d["layers"],
                        embed_dim=d["embed"], num_heads=d["heads"],
                        mlp_dim=d["mlp"], max_len=job["max_len"],
                        remat=False, dtype=jnp.float32)
shape = jax.eval_shape(lambda: TransformerLM(cfg).init(
    jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"])
ck = CheckpointManager(job["ckpt"])
params = ck.restore(TrainState.create(shape, optax.adamw(1e-3)))[0].params
out = {k: [int(t) for t in generate(
           cfg, params, jnp.asarray([p], jnp.int32), job["max_new"],
           temperature=0)[0]]
       for k, p in job["prompts"].items()}
print(json.dumps(out))
"""


def seeded_prompt(seed: int, n: int) -> list[int]:
    """A prompt from a seed, without numpy's help (this process stays
    light): a 31-bit LCG over the vocabulary."""
    x, out = 12345 + seed, []
    for _ in range(n):
        x = (1103515245 * x + 12345) % (1 << 31)
        out.append(1 + x % (DIMS["vocab"] - 1))
    return out


def read(path: str) -> str:
    with open(path, "rb") as f:
        return f.read().decode(errors="replace")


def tail(path: str, n: int = 40) -> str:
    return "\n".join(read(path).splitlines()[-n:])


def cache_entries() -> tuple[str, int]:
    """Where the children keep XLA's compile cache (the rule of
    edl_tpu/utils/compile_cache.py) and how many programs it holds: a
    run that adds none compiled nothing it had not compiled before."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    if not os.path.isdir(path):
        return path, 0
    return path, sum(not f.endswith("-atime") for f in os.listdir(path))


def one_chip(smoke: Smoke, device: dict) -> tuple[str, dict]:
    ckpt = os.path.join(smoke.data, "ckpt")
    with smoke.phase("train"):
        first = smoke.train("train-1", ckpt, 2, device,
                            extra=("--batch_size", "8"))
        if first["resume_epoch"] != 0:
            smoke.fail(f"fresh run resumed at epoch {first['resume_epoch']}")
        # train/checkpoint.py keeps Orbax's data files under 2 x 32 MiB:
        # the machine that checks PRs refuses a larger write (EFBIG)
        sizes = [os.path.getsize(os.path.join(r, f))
                 for r, _, fs in os.walk(ckpt) for f in fs]
        print(f"[chip_smoke] checkpoints: {sum(sizes) >> 20} MiB in "
              f"{len(sizes)} files, the largest {max(sizes) >> 20} MiB",
              flush=True)
        if max(sizes) >= 64 << 20:
            smoke.fail(f"a checkpoint file of {max(sizes)} bytes")
    with smoke.phase("restore+train"):
        second = smoke.train("train-2", ckpt, 3, device,
                             extra=("--batch_size", "8"))
        source = smoke.restore_source("smoke-train-2")
        if second["resume_epoch"] != 2:
            smoke.fail(f"second run resumed at epoch "
                       f"{second['resume_epoch']}, not 2")
        nll = {**first["val_nll"], **second["val_nll"]}
        if not nll[2] < nll[0]:
            smoke.fail(f"held-out NLL is not falling: {nll}")
        print(f"[chip_smoke] val_nll by epoch {nll}; second run restored "
              f"step {2 * STEPS} (restore_source={source}); device memory "
              f"MiB {first['memory_mib']}", flush=True)
    with smoke.phase("refuse-oversize"):
        print(f"[chip_smoke] {smoke.refuse_oversize(ckpt)}", flush=True)
    with smoke.phase("serve"):
        served = smoke.serve("serve", ckpt, device)
        print(f"[chip_smoke] {served['kv_log']}; engine stats "
              f"{served['stats']}", flush=True)
    with smoke.phase("reference"):
        smoke.reference(ckpt, served)
    return ckpt, served


def four_chips(smoke: Smoke, device: dict, ckpt: str,
               served: dict) -> None:
    """dp=4 and dp=2 x tp=2 against ONE chip at the same global batch,
    then a tp=2 replica against the one-chip replica's tokens."""
    runs = {}
    for name, launch, extra in (
            ("mesh-1chip", ("--devices", "0"), ("--tp", "1")),
            ("mesh-dp4", (), ("--tp", "1")),
            ("mesh-dp2tp2", (), ())):
        with smoke.phase(name):
            runs[name] = smoke.train(
                name, os.path.join(smoke.data, name), 2, device,
                extra=("--batch_size", "32", *extra), launch=launch)
            print(f"[chip_smoke] {name}: mesh {runs[name]['mesh']} "
                  f"remat={runs[name]['remat']} val_nll "
                  f"{runs[name]['val_nll']} device memory MiB "
                  f"{runs[name]['memory_mib']}", flush=True)
    want = {"mesh-1chip": (1, "'dp': 1"), "mesh-dp4": (4, "'dp': 4"),
            "mesh-dp2tp2": (4, "'tp': 2")}
    for name, (count, axis) in want.items():
        run = runs[name]
        if run["device_count"] != count or axis not in run["mesh"]:
            smoke.fail(f"{name} ran on {run['device_count']} devices, mesh "
                       f"{run['mesh']}")
        held = ast.literal_eval(run["memory_mib"] or "{}")
        if len(held) != count or not all(v[0] > 0 for v in held.values()):
            smoke.fail(f"{name}: not every device holds state: {held}")
        gap = abs(run["val_nll"][1] - runs["mesh-1chip"]["val_nll"][1])
        if gap > MESH_NLL_TOL:
            smoke.fail(f"{name} val_nll {run['val_nll']} is {gap:.3f} from "
                       f"one chip's {runs['mesh-1chip']['val_nll']} "
                       f"(tolerance {MESH_NLL_TOL})")
    with smoke.phase("serve-tp2"):
        tp2 = smoke.serve("serve-tp2", ckpt, device, extra=("--tp", "2"))
        print(f"[chip_smoke] {tp2['kv_log']}", flush=True)
        if tp2["kv_log"].count("'tp'") != 2:     # slabs and pool
            smoke.fail(f"tp=2 replica did not shard its KV: {tp2['kv_log']}")
        if tp2["answers"] != served["answers"]:
            smoke.fail(f"tp=2 tokens {tp2['answers']} != one-chip tokens "
                       f"{served['answers']}")


def main() -> None:
    t0 = time.monotonic()
    smoke = Smoke()
    cache_dir, cached = cache_entries()
    try:
        with smoke.phase("probe"):
            device = smoke.probe()
        print(f"[chip_smoke] platform={device['platform']} "
              f"device_kind={device['kind']} count={device['count']}; "
              f"logs in {smoke.logs}", flush=True)
        fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
        print(f"[chip_smoke] data in {smoke.data} "
              f"({shutil.disk_usage(smoke.data).free >> 30} GiB free), "
              f"RLIMIT_FSIZE="
              f"{'none' if fsize == resource.RLIM_INFINITY else fsize}",
              flush=True)
        smoke.start_coord()
        ckpt, served = one_chip(smoke, device)
        if device["count"] >= 4:
            four_chips(smoke, device, ckpt, served)
    finally:
        smoke.stop_all()
        shutil.rmtree(smoke.data, ignore_errors=True)
    if "jax" in sys.modules:
        raise SystemExit("chip_smoke FAILED: the parent imported jax")
    print(f"[chip_smoke] compile cache {cache_dir}: {cached} programs "
          f"before this run, {cache_entries()[1] - cached} compiled and "
          f"added by it", flush=True)
    print(f"[chip_smoke] phases (s): {json.dumps(smoke.times)}; total "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
