"""The load generator: a client process that never imports JAX.

Started by ``runners/serve.py`` with the job as one JSON object on its
standard input; talks to the gateway over the EDL1 wire
(``gate_generate``), as a user's client would.  Prints JSON lines:
``{"event": "t0", ...}`` when the window's first instant is fixed
(``time.monotonic()``, which processes of one machine share), and
``{"event": "done", "records": [...]}`` at the end.  A record is one
request with its send and done times on that clock.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from edl_tpu.rpc.client import RpcClient  # noqa: E402

from generators import closed_sessions  # noqa: E402


def prompt_key(prompt: list[int]) -> str:
    """Joins a client's record with the spans the server side keeps for
    the same request (the gateway's request id never leaves it)."""
    import numpy as np
    return hashlib.sha1(np.asarray(prompt, np.int32).tobytes()).hexdigest()[:16]


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def ask(client: RpcClient, prompt: list[int], max_new: int,
        session: str | None, rec: dict) -> list[int] | None:
    rec.update(n_prompt=len(prompt), max_new=max_new, key=prompt_key(prompt))
    rec["t_send"] = time.monotonic()
    try:
        kw = {} if session is None else {"session": session}
        out = client.call("gate_generate", prompt=prompt, max_new=max_new,
                          timeout=300.0, _timeout=330.0, **kw)
        toks = out["tokens"]
        rec.update(t_done=time.monotonic(), n_got=len(toks),
                   ok=len(toks) == max_new)
        if not rec["ok"]:
            rec["err"] = f"{len(toks)} tokens for {max_new} asked"
        return toks
    except Exception as e:  # noqa: BLE001 — a failed request is a record
        rec.update(t_done=time.monotonic(), n_got=0, ok=False,
                   err=f"{type(e).__name__}: {e}"[:200])
        return None


def run_open(job: dict) -> None:
    """Send each request at its due time from a pool of worker threads,
    each with its own connection; a request that finds no idle worker
    waits in the queue and its lateness is recorded."""
    reqs = job["plan"]["requests"]
    t0 = time.monotonic() - reqs[0]["due"] + 1.0
    emit({"event": "t0", "t0": t0, "seconds": job["seconds"]})
    work: queue.Queue = queue.Queue()
    records: list[dict] = []
    lock = threading.Lock()

    def worker():
        client = RpcClient(job["endpoint"], 330.0)
        while True:
            r = work.get()
            if r is None:
                client.close()
                return
            rec = {"i": r["i"], "window": r["window"],
                   "t_due": t0 + r["due"]}
            ask(client, r["prompt"], r["max_new"], None, rec)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(job["traffic"]["client_threads"])]
    for t in threads:
        t.start()
    for r in reqs:
        wait = t0 + r["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        work.put(r)
    deadline = t0 + job["seconds"] + job["traffic"]["drain_seconds"]
    while time.monotonic() < deadline:
        with lock:
            if len(records) == len(reqs):
                break
        time.sleep(0.05)
    for _ in threads:
        work.put(None)
    with lock:
        done = list(records)
    seen = {(r["i"], r["window"]) for r in done}
    for r in reqs:      # still in flight at the drain deadline: failed
        if (r["i"], r["window"]) not in seen:
            done.append({"i": r["i"], "window": r["window"],
                         "t_due": t0 + r["due"], "t_send": None,
                         "t_done": None, "n_prompt": len(r["prompt"]),
                         "max_new": r["max_new"], "n_got": 0, "ok": False,
                         "err": "not answered by the drain deadline"})
    emit({"event": "done", "t0": t0, "records": done})


def run_closed(job: dict) -> None:
    """``clients`` threads, each walking the cycle of sessions from its
    own start point; the window opens once every client has finished
    ``warm_turns`` turns, and closes ``seconds`` later: a client stops
    after the turn it is in."""
    plan = job["plan"]
    n_sessions = len(plan["doc_lens"])
    n_q = len(plan["question_lens"][0])
    state = {"t0": None, "t_end": None}
    warm_left = threading.Semaphore(0)
    records: list[dict] = []
    lock = threading.Lock()

    def client_loop(c: dict):
        import numpy as np
        client = RpcClient(job["endpoint"], 330.0)
        fake = np.random.default_rng(plan["id_seed"] + [c["client"], 99])
        s, turn, lap, done_turns = c["first_session"], c["first_turn"], 0, 0
        while True:
            doc, qs = closed_sessions.session_ids(plan, s, lap, c["client"])
            history = list(doc)
            for j in range(turn):    # entered mid-session (warm-up only)
                history += qs[j] + fake.integers(
                    1, plan["vocab"], plan["output_tokens"]).tolist()
            sid = f"c{c['client']}-s{s}-l{lap}"
            while turn < n_q:
                if state["t_end"] is not None and \
                        time.monotonic() >= state["t_end"]:
                    client.close()
                    return
                prompt = history + qs[turn]
                rec = {"client": c["client"], "session": s, "lap": lap,
                       "turn": turn}
                toks = ask(client, prompt, plan["output_tokens"],
                           sid if job["traffic"]["session_affinity"] else None,
                           rec)
                with lock:
                    records.append(rec)
                done_turns += 1
                if done_turns == plan["warm_turns"]:
                    warm_left.release()
                if toks is None:
                    break            # abandon the session, start the next
                history = prompt + toks
                turn += 1
                if plan["think_seconds"]:
                    time.sleep(plan["think_seconds"])
            s, turn = (s + 1) % n_sessions, 0
            lap += s == c["first_session"]

    threads = [threading.Thread(target=client_loop, args=(c,), daemon=True)
               for c in plan["clients"]]
    for t in threads:
        t.start()
    for _ in threads:
        warm_left.acquire()
    state["t0"] = time.monotonic() + 0.25
    state["t_end"] = state["t0"] + job["seconds"]
    emit({"event": "t0", "t0": state["t0"], "seconds": job["seconds"]})
    for t in threads:
        t.join(job["seconds"] + job["traffic"]["drain_seconds"])
    with lock:
        done = list(records)
    emit({"event": "done", "t0": state["t0"], "records": done,
          "stuck_clients": sum(t.is_alive() for t in threads)})


def main() -> None:
    job = json.loads(sys.stdin.readline())
    if job["plan"]["loop"] == "open":
        run_open(job)
    else:
        run_closed(job)


if __name__ == "__main__":
    main()
