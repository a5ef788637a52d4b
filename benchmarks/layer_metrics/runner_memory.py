"""Reader of the three runner-memory metrics: what a runner holds on the chip.

Since PR 48 the program stamps each `runner.compile` phase of its set-up ledger
(`worker/worker.py` `_enqueue`, a runner miss) with what the compiled executable
holds on one device, by its own `memory_analysis()`: `code_bytes`, `temp_bytes`,
`argument_bytes`, `output_bytes`, `alias_bytes`; and with `state_bytes`, one
device's shards of the state operands handed to that dispatch.  The view is
`setup_phase.ledger(run)`'s: the phases closed before the window.

A metric file names its `quantity`.  A program from before the stamp, and a
backend or an executable without an analysis, leave the fields out and so every
metric out, never 0.  Once a traced run the reader logs each runner's fields
beside the allocator's growth over the phase (`bytes_in_use`, close less open):
the check that `code_bytes + output_bytes - alias_bytes` and the chip's
allocator speak of the same bytes.
"""

from benchmarks.layer_metrics import setup_phase

STAMPED = ("code_bytes", "temp_bytes", "argument_bytes", "output_bytes",
           "alias_bytes", "state_bytes")
QUANTITIES = {
    "code": lambda held: sum(h["code_bytes"] for h in held),
    "state": lambda held: max(
        h["state_bytes"] + h["output_bytes"] - h["alias_bytes"] for h in held),
    "temp": lambda held: max(h["temp_bytes"] for h in held),
}


def runners(run):
    """The stamped `runner.compile` records' args with the allocator's growth
    over each (`grew`, None without `bytes_in_use`), logged once; None from a
    program without the ledger."""
    view = setup_phase.ledger(run)
    if view is None:
        return None
    if "runner_memory" in run.__dict__:
        return run.runner_memory
    run.runner_memory = []
    for r in view["records"]:
        args = r["args"]
        if r["name"] != "runner.compile" or not all(k in args for k in STAMPED):
            continue
        held = dict(args, grew=None)
        if "bytes_in_use" in r:
            held["grew"] = r["bytes_in_use"]["close"] - r["bytes_in_use"]["open"]
        run.runner_memory.append(held)
        placed = args["code_bytes"] + args["output_bytes"] - args["alias_bytes"]
        run.log(
            f"runner memory: {args.get('app')} {args.get('mode')} x{args.get('batch')}: "
            + ", ".join(f"{k} {args[k]}" for k in STAMPED)
            + f"; code + output - alias {placed}, the allocator grew " + (
                "not known" if held["grew"] is None else
                f"{held['grew']} over the phase ({held['grew'] - placed:+d})"))
    if not run.runner_memory:
        run.log("runner memory: no runner.compile phase carries an analysis")
    return run.runner_memory


def read(run, spec):
    held, quantity = runners(run), spec["quantity"]
    if quantity not in QUANTITIES:
        raise ValueError(f"runner_memory: unknown quantity {quantity!r}")
    return QUANTITIES[quantity](held) if held else None
