"""Device time of one add plus one sample of the replay ring, from the trace:
the mean execution of the add program plus the mean execution of the sample
program, found by module name (as `train_step_ms` finds the train step), so
the figure does not depend on the shape the ring is stored in."""

PROGRAMS = ("_store_add_packed", "_store_sample")


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    runs = {
        program: [s for name, each in trace["modules"].items() if program in name for s in each]
        for program in PROGRAMS
    }
    if not all(runs.values()):  # half the sum under the whole's name would be a wrong number
        return None
    means = {program: sum(each) / len(each) for program, each in runs.items()}
    run.setdefault("notes", []).extend(
        f"replay_programs_ms: {program} x{len(runs[program])}, mean {1e3 * mean:.4f} ms"
        for program, mean in means.items()
    )
    return 1e3 * sum(means.values())
