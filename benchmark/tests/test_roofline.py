"""The byte functions on known shapes, and the reader on a made-up trace."""
from readers import trace_program_roofline as reader
from roofline import bytes as rb


def test_bytes_on_known_shapes():
    assert rb.pad_pow2(15000) == 16384 and rb.pad_pow2(5000) == 8192
    assert rb.pad_pow2(3, 16) == 16 and rb.pad_pow2(1000, 16) == 1024
    # one row: 69 bytes read (1 + 3x8 + 5x8 + 4), 40 written
    assert rb.NODE_READ == 69 and rb.NODE_WRITE == 40
    assert rb.schedule_batch_uniform(16384, 10000) == 16384 * 109 + 40000
    assert rb.schedule_batch(8192, 1000) == 8192 * 125 + 4000
    assert rb.scatter_rows(16384, 10000, 15000) == 16384 * (2 * 101 + 4)
    assert rb.scatter_rows(16384, 3, 15000) == 16 * (2 * 101 + 4)
    # a floor grows with rows and with pods, and never counts a plane per pod
    assert rb.schedule_batch(8192, 2000) - rb.schedule_batch(8192, 1000) == 4000


def test_reader_on_a_made_up_trace():
    ctx = {"trace": {"devices": 1, "modules": {
        "jit__scatter_rows": {"seconds": 0.150, "launches": 7.0}}},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "cfg": {"nodes": {"count": 15000}}, "trace_pods_bound": 70000}
    got = reader.read(ctx, "jit__scatter_rows", "scatter_rows")
    want = 100.0 * (16384 * 206 * 7 / 819e9) / 0.150
    assert abs(got - want) < 1e-12 and 0 < got < 1
    assert reader.read(ctx, "jit__absent", "scatter_rows") is None
    assert reader.read({**ctx, "trace": None}, "jit__scatter_rows",
                       "scatter_rows") is None
