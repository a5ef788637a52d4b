from benchmarks.layer_metrics import lcc_list_bytes
from benchmarks.layer_metrics.lcc_scope import lcc_stats, traced_queries


def read(run, spec):
    t = run.trace
    peaks = run.peaks["devices"].get(run.devices[0].device_kind)
    if not t or peaks is None or lcc_stats(run) is None:
        return None  # no table of peaks for this device, or no LCC in the program
    floor = lcc_list_bytes.for_run(run)["list_bytes"] / run.chips / peaks["hbm_bytes_per_s"]
    return 100.0 * floor / (t["busy_s"] / traced_queries(run))
