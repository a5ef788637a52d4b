"""Callers that each wait for a reply, in front of one `ServeSession`.

The traffic file gives the callers and, per job, which callers ask it and
the rule for its keys; callers of one job share its key list and each
takes the next key.  The configuration gives the session's `BatchPolicy`.
One thread plays every caller: it submits for each idle caller, pumps the
session once, hands each delivered answer to its caller, and that caller
asks again at once (zero think time).  Latency is the benchmark's own
clock from before `submit` to the return of the pump that delivered.  None
is submitted after the deadline; what is queued or in flight then
finishes, and the window is the time actually covered.
"""

from __future__ import annotations

import time

from benchmarks.drivers.common import job_keys, median, spread_line


class Driver:
    def __init__(self, run):
        from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

        self.run = run
        self.session = ServeSession(
            run.frag, policy=BatchPolicy(**run.config["serve_policy"]))
        self.callers = {}  # caller -> its job
        for job in run.traffic["jobs"]:
            state = {"app": job["app"], "params": dict(job.get("params", {})),
                     "key_param": job["keys"]["param"],
                     "keys": job_keys(run, job), "asked": 0}
            for c in job["callers"]:
                self.callers[int(c)] = state
        if sorted(self.callers) != list(range(int(run.traffic["callers"]))):
            raise ValueError("traffic: every caller needs exactly one job")
        self.answers, self.batches = [], []  # the window's
        self.traced = []
        self.covered_s = None

    def _submit(self, caller: int) -> dict:
        job = self.callers[caller]
        params = dict(job["params"])
        params[job["key_param"]] = job["keys"][job["asked"] % len(job["keys"])]
        job["asked"] += 1
        out = {"caller": caller, "app": job["app"], "params": params,
               "t_submit": time.perf_counter()}
        with self.run.span("bench.serve.submit"):
            out["request"] = self.session.submit(job["app"], params)
        return out

    def _loop(self, deadline: float | None, answers: list, batches: list) -> None:
        """Closed loop until `deadline` (None: one request per caller), then
        until nothing is outstanding."""
        outstanding = {c: self._submit(c) for c in sorted(self.callers)}
        while outstanding:
            with self.run.span("bench.serve.pump"):
                delivered = self.session.pump()
            now = time.perf_counter()
            if delivered:
                first = delivered[0]
                batches.append({"lanes": len(delivered), "app": first.app_key,
                                "stages": dict(first.stages or {})})
            for c in sorted(outstanding):
                o = outstanding[c]
                res = o["request"].result
                if res is None:
                    continue
                del outstanding[c]
                o.update(latency_s=now - o["t_submit"], t_done=now, ok=bool(res.ok),
                         error=res.error, stages=dict(res.stages or {}),
                         values=res.values if res.ok else None)
                del o["request"]
                answers.append(o)
                if deadline is not None and now < deadline:
                    outstanding[c] = self._submit(c)

    def warm_up(self) -> None:
        """One cycle: every caller asks once, which compiles (or fetches)
        each app's batched runner at the lane count the loop settles at."""
        warm, batches = [], []
        self._loop(None, warm, batches)
        for job in {id(j): j for j in self.callers.values()}.values():
            job["asked"] = 0
        bad = [a for a in warm if not a["ok"]]
        if bad:
            raise RuntimeError(f"warm-up failed: {bad[0]['error']}")
        self.run.log("warm-up batches: " + ", ".join(
            f"{b['app']} x{b['lanes']}" for b in batches))

    def traced_pass(self) -> None:
        self._loop(None, self.traced, [])

    def measure(self, seconds: float) -> None:
        hist0 = dict(self.session.queue.batch_hist)
        t0 = time.perf_counter()
        self._loop(t0 + seconds, self.answers, self.batches)
        self.covered_s = max(a["t_done"] for a in self.answers) - t0
        hist = self.session.queue.batch_hist
        self.batch_hist = {k: v - hist0.get(k, 0) for k, v in hist.items()
                           if v - hist0.get(k, 0)}

    def describe_samples(self) -> str:
        return (spread_line("query_latency_s",
                            [a["latency_s"] for a in self.answers if a["ok"]])
                + f"; batches {self.batch_hist}; covered {self.covered_s:.3f} s")

    def check(self):
        failed = 0
        for a in self.answers + self.traced:
            a["correct"] = False
            if not a["ok"]:
                failed += 1
                self.run.log(f"FAILED: {a['app']} {a['params']}: {a['error']}")
                continue
            bad = self.run.wrong_vertices(a["app"], a["params"], a.pop("values"))
            if bad:
                failed += 1
                self.run.log(f"WRONG: {a['app']} {a['params']}: {bad} vertices "
                             "off the plain reference")
            a["correct"] = not bad
        return len(self.answers) + len(self.traced), failed

    def end_to_end(self) -> dict:
        good = [a for a in self.answers if a.get("correct")]
        return {"served_qps": len(good) / self.covered_s,
                "query_latency_s": median([a["latency_s"] for a in good])}

    def readings(self) -> dict:
        good = [a for a in self.answers if a.get("correct")]
        lanes = sum(k * v for k, v in self.batch_hist.items())
        nb = sum(self.batch_hist.values())
        return {
            "serve_queue_wait_ms": median(
                [a["stages"].get("queue_wait_us", 0) / 1e3 for a in good]),
            "serve_lane_ms": median(
                [b["stages"].get("device_us", 0) / 1e3 / b["lanes"]
                 for b in self.batches]),
            "serve_batch_lanes": lanes / nb if nb else None,
        }

    def close(self) -> None:
        self.session.close()
