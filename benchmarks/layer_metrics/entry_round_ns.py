def read(run, spec):
    r = run.readings
    if not (r.get("proc_time_s") and r.get("rounds")):
        return None
    return r["proc_time_s"] / r["rounds"] / run.dataset_info["pull_entries"] * 1e9
