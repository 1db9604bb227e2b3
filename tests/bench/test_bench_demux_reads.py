"""The per-layer reader of the demux's device reads
(``demux_device_reads.stream``): a delta of the program's ``stats()``
over the window, ``None`` where the program has no such counter, and 0
on a served stream that collects nothing."""
from _tiny import ROOT

from bench.run import load_reader

NAME = "demux_device_reads.stream"


def _run(before, after):
    return dict(stats_before=before, stats_after=after, window_s=4.0)


def test_reader_by_hand():
    read = load_reader(ROOT, "layers", NAME)
    assert read(_run(dict(demux_device_reads=3),
                     dict(demux_device_reads=10))) == 7.0


def test_reader_none_without_the_counter():
    read = load_reader(ROOT, "layers", NAME)
    bare = dict(demux_s=0.0)
    assert read(_run(bare, dict(bare, demux_s=1.0))) is None
    assert read(_run(bare, dict(demux_device_reads=2))) is None


def test_reader_on_a_served_stream():
    from repro import MBEClient, MBEOptions
    from repro.data.generators import dense_small
    client = MBEClient(MBEOptions(max_batch=2, steps_per_round=8))
    client.enumerate_many([dense_small(7, 12, p=0.4, seed=1, name="w")])
    before = client.stats()
    futs = [client.submit(dense_small(6 + i % 3, 12, p=0.35, seed=60 + i,
                                      name=f"g{i}")) for i in range(6)]
    while not all(f.done() for f in futs):
        client.poll()
    assert load_reader(ROOT, "layers", NAME)(
        _run(before, client.stats())) == 0.0
