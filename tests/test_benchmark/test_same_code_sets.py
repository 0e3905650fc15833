"""`run_seconds` and the bound of `env_steps_per_s` are seated on sets of runs
of one program, kept in `data/same_code_sets.json`: six runs one after another
on one machine, read at the manifest's window. By the check's ruler (the
middle half of a set, its farthest run left out) every set spreads at most
half the manifest's bound, and by the ledger's (the range, likewise) at most
the bound; a set that does not is named in the file (`"reads": "over half the
bound"`) and shows here as an expected failure, not averaged away. A
`benchmark` PR that moves either number brings sets of its own."""

import statistics

import pytest

from .conftest import DATA, load

SETS = load(f"{DATA}/same_code_sets.json")["sets"]
METRIC = "env_steps_per_s"
OVER = "over half the bound"


def without_the_farthest(values):
    median = statistics.median(values)
    kept = list(values)
    kept.remove(max(values, key=lambda v: abs(v - median)))
    return kept


def spread(values):
    """The ledger's ruler, as an `unresolved` verdict words it and holds it to
    the whole bound: the range of the set, with the run farthest from the
    set's median left out where that narrows it, as a share of that median."""
    kept = without_the_farthest(values)
    widths = [max(values) - min(values)] + ([max(kept) - min(kept)] if len(kept) > 1 else [])
    return min(widths) / statistics.median(values)


def middle_half(values):
    """The check's ruler, as its refusal of a bound words it and holds it to
    half the bound: the first to the third quartile (`statistics.quantiles`)
    of the set with its farthest run left out, as a share of the set's median."""
    q = statistics.quantiles(without_the_farthest(values), n=4)
    return (q[2] - q[0]) / statistics.median(values)


def rate_at(iteration_seconds, num_envs, seconds):
    """env steps/s of the window that closes at the first boundary `seconds`
    or more after the opening (the harness's rule: a run at 51 s holds, in its
    stamps, the run every shorter window would have been); None where the
    stamps end before it."""
    elapsed = 0.0
    for count, dt in enumerate(iteration_seconds, 1):
        elapsed += dt
        if elapsed >= seconds:
            return count * num_envs / elapsed
    return None


def stalls(iteration_seconds, factor=2.0):
    """[window index, ms, seconds since the opening] of every iteration over
    `factor` x the run's median."""
    median = statistics.median(iteration_seconds)
    out, elapsed = [], 0.0
    for i, dt in enumerate(iteration_seconds):
        if dt > factor * median:
            out.append([i, round(1e3 * dt, 1), round(elapsed, 2)])
        elapsed += dt
    return out


@pytest.mark.parametrize("values,expected", [
    ([10.0, 10.1, 10.2, 10.3, 12.0], 0.3 / 10.2),  # odd count: the far run goes
    ([1.0, 2.0, 3.0, 4.0], 2.0 / 2.5),  # even count, two equally far: either leaves 2
    ([5.0, 5.0, 5.0, 7.0, 7.0, 7.0], 2.0 / 6.0),  # leaving one out narrows nothing
])
def test_the_ruler_on_hand_made_sets(values, expected):
    assert spread(values) == pytest.approx(expected)


def test_the_checks_ruler_on_a_hand_made_set():
    # 10 is the farthest from the median 20.5; of 20, 20, 21, 22 and 23 the quartiles are 20 and 22.5
    assert middle_half([10.0, 20.0, 20.0, 21.0, 22.0, 23.0]) == pytest.approx(2.5 / 20.5)
    assert middle_half([5.0, 5.0, 5.0, 5.0, 5.0, 9.0]) == 0.0  # one far-off run in a set does no harm


def bound_of(manifest):
    return next(m["bound"] for m in manifest["end_to_end"] if m["name"] == METRIC)


def named(entry):
    marks = [pytest.mark.xfail(strict=True, reason=f"{entry['cell']} {entry['set']}: {OVER}")] if entry.get("reads") == OVER else []
    return pytest.param(entry, id=f"{entry['cell']}-{entry['set']}", marks=marks)


@pytest.mark.parametrize("entry", [named(s) for s in SETS if len(s["seeds"]) >= 6])
def test_a_set_read_at_the_manifests_window_spreads_at_most_half_the_bound(manifest, entry):
    """Where the check stops calling the bound too tight, and where a PR that
    claims nothing gets a verdict."""
    rates = entry["rates"][str(manifest["run_seconds"])]
    assert len(rates) == len(entry["seeds"]) == len(set(entry["seeds"]))
    assert middle_half(rates) <= bound_of(manifest) / 2
    assert spread(rates) <= bound_of(manifest)


@pytest.mark.parametrize("entry", SETS, ids=lambda s: f"{s['cell']}-{s['set']}")
def test_a_set_is_whole_and_no_run_compiles_inside_the_manifests_window(manifest, entry):
    """A run that compiles in its window reads `failed`: the window closes
    before the first compile any run of the set met (a second or more)."""
    window = manifest["run_seconds"]
    assert entry["cell"] in {w["name"] for w in manifest["workloads"]}
    assert entry["tree"] and all(entry["correct"]) and entry.get("reads") in (None, OVER)
    assert max(float(w) for w in entry["rates"]) == entry["ran_seconds"] >= window
    assert all(len(column) == len(entry["seeds"]) for column in (*entry["rates"].values(), entry["setup_s"], entry["slow"], entry["compile_at_s"], entry["correct"]))
    assert all(at is None or at > window + 1 for at in entry["compile_at_s"])


@pytest.mark.parametrize("cell", sorted({s["cell"] for s in SETS}))
def test_a_cell_has_two_sets_through_the_literal_command_at_the_manifests_window(manifest, cell):
    """Beside the sets read off longer runs' stamps: two sets of six on the
    same seeds, run at `run_seconds` from an archive of the tree."""
    fresh = [s for s in SETS if s["cell"] == cell and s["set"].startswith("fresh") and s["ran_seconds"] == manifest["run_seconds"]]
    assert len(fresh) >= 2 and all(s["seeds"] == fresh[0]["seeds"] for s in fresh)
    # two sets of one program: the driver holds the second's median to the first's by the bound
    medians = [statistics.median(s["rates"][str(manifest["run_seconds"])]) for s in fresh]
    assert max(medians) / min(medians) - 1 <= bound_of(manifest)


@pytest.mark.parametrize("cell", sorted({s["cell"] for s in SETS}))
def test_the_runs_the_window_was_chosen_from_hold_every_window(manifest, cell):
    """Step 0: twelve runs a cell at 51 s on the parent's tree, the windows of
    20 (the one before PR 32), 35 and 51 s read off the same stamps."""
    body = [s for s in SETS if s["cell"] == cell and s["set"].startswith("step0")]
    seeds = [seed for s in body for seed in s["seeds"]]
    assert len(seeds) == len(set(seeds)) >= 12
    assert all(set(s["rates"]) == {"20", "35", "51"} and s["ran_seconds"] == 51 for s in body)


def test_a_run_that_compiles_reads_like_one_that_does_not_since_the_harness_hands_memory_back():
    """The check's sets each begin with a run that compiles. Before, that run
    held seconds at window index 130 (memory handed back after compiling) and
    read 6 % low: the one run every ruler leaves out, so the one machine stall
    a set meets was kept. Since set-up ends by handing the memory back, no run
    holds index 130 among its three slowest and the compiling run is inside
    the bound of the others."""
    cell = "dv3_s_bf16.ratio64"
    before, after = (next(s for s in SETS if s["cell"] == cell and s["set"] == name) for name in ("refused-H", "handback-I"))
    for entry, stalled in ((before, True), (after, False)):
        assert entry["compiled"][0] and not any(entry["compiled"][1:])
        rates = entry["rates"][str(entry["ran_seconds"])]
        low = 1 - rates[0] / statistics.median(rates[1:])
        at_130 = [ms for index, ms in entry["slow"][0] if index == 130]
        assert (low > 0.05 and at_130 and at_130[0] > 2000) if stalled else (low < 0.05 and not at_130)
    assert not any(index == 130 for run in after["slow"] for index, _ in run)
    assert after["hand_back_s"][0] > 2.0 > 0.2 > max(after["hand_back_s"][1:])


def test_the_parents_traffic_compiles_inside_a_51_s_window_of_ratio64():
    """Why `run_seconds` is not 51: at step 1,286 two of the 16 environments
    end an episode together (test_traffic.py), the program adds a step's ends
    in one call shaped by their number, and every run of the parent's tree met
    that compile 46 to 49 s into its window: a `failed` run."""
    body = [s for s in SETS if s["cell"] == "dv3_s_bf16.ratio64" and s["set"].startswith("step0")]
    met = [at for s in body for at in s["compile_at_s"]]
    assert len(met) == 12 and all(at is not None and 46.0 < at < 49.0 for at in met)


def test_a_shorter_window_is_read_off_a_longer_runs_stamps_by_the_harnesss_rule():
    """The window closes at the first boundary `seconds` or more after the
    opening (drivers/train_main.py `Window`): the same stamps, cut there."""
    from benchmark.drivers.train_main import Window

    stamps = [0.0]
    for i in range(400):
        stamps.append(stamps[-1] + (0.3 if i == 130 else 0.04))  # one slow iteration
    its = [b - a for a, b in zip(stamps, stamps[1:])]
    for seconds in (2.0, 5.0, 9.99, 16.0):
        window = Window(seconds, open_at=1, stop=lambda: None)
        for now in stamps:
            window.on_step(now)
        assert rate_at(its, 16, seconds) == pytest.approx(16 * (window.i_close - window.i_open) / window.window_seconds)
    assert rate_at(its, 16, 17.0) is None  # the stamps end before it
    assert stalls(its) == [[130, 300.0, pytest.approx(5.2)]]
