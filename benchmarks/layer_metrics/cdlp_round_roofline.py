from benchmarks.layer_metrics import cdlp_pass_bytes, cdlp_scope_per_pass


def read(run, spec):
    t, n = run.trace, cdlp_scope_per_pass.passes(run)
    peaks = run.peaks["devices"].get(run.devices[0].device_kind)
    if not t or not n or peaks is None:
        return None  # no table of peaks for this device: no roofline
    floor = cdlp_pass_bytes.cdlp_pass_floor_s(
        run.dataset_info["pull_entries"], run.dataset_info["vertices"],
        run.chips, peaks["hbm_bytes_per_s"])
    return 100.0 * floor / (t["busy_s"] / n)
