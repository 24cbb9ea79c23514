"""The closed-loop mapping driver's window on the CPU, with a stub
mapper that answers on a simulated clock: the window ends at its
deadline while the read pool lasts, and only a pool that truly runs out
ends the run in `BenchError`."""

import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import loader  # noqa: E402
import program  # noqa: E402

CELL = "sr_map.closed"
SECONDS = 51.0


class Clock:
    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


class StubMapper:
    """Answers every read at once (recall is not read here), refuses a
    read it has mapped before, and moves the clock on as a mapper of
    `rate` reads/s would."""

    def __init__(self, clock, rate):
        self.clock, self.rate = clock, rate
        self.seen = set()

    def map_batch(self, reads):
        for read in reads:
            assert id(read) not in self.seen, "a read mapped twice"
            self.seen.add(id(read))
        self.clock.now += len(reads) / self.rate
        return [types.SimpleNamespace(status="mapped", strand=0,
                                      ref_start=-1, band=1) for _ in reads]


class StubService:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def run_driver(monkeypatch, rate):
    spec = loader.load_benchmark()
    cell = loader.find_cell(spec, CELL)
    config = dict(loader.load_config(spec, cell["config"]),
                  genome_bp=200_000)
    traffic = loader.load_traffic(cell["traffic"])
    driver = loader.load_driver(traffic["driver"])
    clock = Clock()
    mapper = StubMapper(clock, rate)
    monkeypatch.setattr(driver, "time", clock)
    monkeypatch.setattr(harness, "time", clock)
    monkeypatch.setattr(program, "make_index",
                        lambda genome, cfg: types.SimpleNamespace(
                            lookup=None))
    monkeypatch.setattr(program, "make_service",
                        lambda cfg, devices: StubService())
    monkeypatch.setattr(program, "make_mapper",
                        lambda index, service, cfg: mapper)
    monkeypatch.setattr(program, "service_counters", lambda service: {})
    monkeypatch.setattr(program, "mapped_status", lambda: "mapped")
    ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                          seed=2**31 + 5, seconds=SECONDS, trace=False,
                          devices=None, t_process=0.0,
                          compiles=harness.CompileCounter())
    return ctx, traffic, driver.run(ctx)


@pytest.mark.parametrize("rate", [80.0, 1000.0, 3000.0])
def test_the_window_ends_at_its_deadline_while_the_pool_lasts(monkeypatch,
                                                              rate):
    ctx, traffic, record = run_driver(monkeypatch, rate)
    batch = record["batch_reads"]
    assert batch == 2048
    # The last batch starts before the deadline and ends after it.
    assert ctx.t_window_end >= ctx.t_window + SECONDS
    assert ctx.t_window_end - batch / rate < ctx.t_window + SECONDS
    assert record["failed"] == 0
    assert record["reads"] % batch == 0
    assert record["reads"] == pytest.approx(rate * SECONDS, abs=batch)
    assert record["reads"] < traffic["pool_reads"]
    if rate >= 1000:  # past where a pool of one chunk ran out
        assert record["reads"] > traffic["pool_chunk_reads"]


def test_a_pool_that_runs_out_ends_the_run(monkeypatch):
    rate = 3400.0  # 51 s need 84 batches of 2,048; the pool holds 80
    with pytest.raises(loader.BenchError, match="exhausted"):
        run_driver(monkeypatch, rate)

