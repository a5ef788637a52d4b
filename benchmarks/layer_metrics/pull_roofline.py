from benchmarks import roofline


def read(run, spec):
    t, rounds = run.trace, run.readings.get("traced_rounds")
    peaks = run.peaks["devices"].get(run.devices[0].device_kind)
    if not t or not rounds or peaks is None:
        return None  # no table of peaks for this device: no roofline
    apps = {j["app"] for j in run.traffic["jobs"]}
    floor = roofline.pull_round_floor_s(
        run.dataset_info["pull_entries"], run.dataset_info["vertices"],
        bool(apps & set(roofline.WEIGHTED_APPS)), run.chips,
        peaks["hbm_bytes_per_s"])
    return 100.0 * floor / (t["busy_s"] / rounds)
