"""One caller, closed loop: a whole `Worker.query` on the resident fragment,
again and again, for the window.

The traffic file's `jobs` are asked round robin.  A job is an app, its
parameters and, where it takes one, the rule for its keys.  A window holds
whole queries only: none starts after the deadline, the one in flight
finishes.  The processing time of a query is the wall of `Worker.query`
(enqueue through `block_until_ready` and the rounds read-back); extracting
the answer for the check is timed apart.
"""

from __future__ import annotations

import time

from benchmarks.drivers.common import job_keys, median, spread_line


class Driver:
    def __init__(self, run):
        from libgrape_lite_tpu.models import APP_REGISTRY
        from libgrape_lite_tpu.worker.worker import Worker

        self.run = run
        self.jobs = []
        for job in run.traffic["jobs"]:
            self.jobs.append({
                "app": job["app"], "params": dict(job.get("params", {})),
                "key_param": (job.get("keys") or {}).get("param"),
                "keys": job_keys(run, job), "asked": 0,
                "worker": Worker(APP_REGISTRY[job["app"]](), run.frag)})
        self.samples = []  # the window's queries
        self.traced = []  # the traced pass's queries, kept apart

    def _ask(self, job: dict, into: list) -> None:
        params = dict(job["params"])
        if job["keys"] is not None:
            params[job["key_param"]] = job["keys"][job["asked"] % len(job["keys"])]
        job["asked"] += 1
        worker = job["worker"]
        sample = {"app": job["app"], "params": params, "error": None}
        into.append(sample)
        try:
            with self.run.span("bench.query"):
                t0 = time.perf_counter()
                worker.query(**params)
                sample["wall_s"] = time.perf_counter() - t0
            with self.run.span("bench.extract"):
                sample["values"] = worker.result_values()
            sample["rounds"] = int(worker.rounds)
            stages = worker.last_stage_ns or {}
            sample["dispatch_ms"] = stages.get("dispatch", 0) / 1e6
        except Exception as e:  # a failed query is counted, not fatal
            sample["error"] = f"{type(e).__name__}: {e}"
            self.run.log(f"query failed: {sample['app']} {params}: {sample['error']}")

    def warm_up(self) -> None:
        """One query per job kind: compiles (or fetches) its runner."""
        for job in self.jobs:
            warm = []
            self._ask(job, warm)
            job["asked"] = 0  # the window starts from the first key
            if warm[0]["error"]:
                raise RuntimeError(f"warm-up failed: {warm[0]['error']}")
            self.run.log(f"warm-up {job['app']}: {warm[0]['wall_s']:.3f} s, "
                         f"{warm[0]['rounds']} rounds")

    def traced_pass(self) -> None:
        for job in self.jobs:
            self._ask(job, self.traced)

    def measure(self, seconds: float) -> None:
        deadline = time.perf_counter() + seconds
        i = 0
        while time.perf_counter() < deadline:
            self._ask(self.jobs[i % len(self.jobs)], self.samples)
            i += 1

    def _good(self) -> list:
        return [s for s in self.samples if not s["error"]]

    def describe_samples(self) -> str:
        good = self._good()
        by_rounds: dict = {}
        for s in good:
            by_rounds.setdefault(s["rounds"], []).append(s["wall_s"])
        return (spread_line("proc_time_s", [s["wall_s"] for s in good]) + "; " + ", ".join(
            f"{len(ws)} of {r} rounds (median {median(ws):.6g} s)"
            for r, ws in sorted(by_rounds.items())))

    def check(self):
        failed = 0
        for s in self.samples + self.traced:
            if s["error"]:
                failed += 1
                continue
            bad = self.run.wrong_vertices(s["app"], s["params"], s.pop("values"))
            if bad:
                failed += 1
                self.run.log(f"WRONG: {s['app']} {s['params']}: {bad} vertices "
                             "off the plain reference")
        return len(self.samples) + len(self.traced), failed

    def end_to_end(self) -> dict:
        return {"proc_time_s": median([s["wall_s"] for s in self._good()])}

    def readings(self) -> dict:
        good = self._good()
        return {
            "dispatch_ms": median([s["dispatch_ms"] for s in good]),
            "rounds": median([s["rounds"] for s in good]),
            "traced_rounds": sum(s.get("rounds", 0) for s in self.traced),
        }

    def close(self) -> None:
        pass
