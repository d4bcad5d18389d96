import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from dss_ml_at_scale_tpu.data import (
    ParquetShardReader,
    TransformSpec,
    batch_loader,
    list_row_groups,
    make_batch_reader,
    shard_units,
    write_delta,
)
from dss_ml_at_scale_tpu.data.transform import Field


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """8 parquet files × 2 row groups × 16 rows = 256 rows."""
    root = tmp_path_factory.mktemp("ds")
    n = 0
    for f in range(8):
        t = pa.table(
            {
                "id": pa.array(np.arange(n, n + 32)),
                "value": pa.array(np.arange(n, n + 32, dtype=np.float64)),
            }
        )
        pq.write_table(t, root / f"part-{f}.parquet", row_group_size=16)
        n += 32
    return root


def test_list_and_shard_units(dataset):
    units = list_row_groups(sorted(str(p) for p in dataset.glob("*.parquet")))
    assert len(units) == 16
    assert all(u.num_rows == 16 for u in units)
    shards = [shard_units(units, i, 4, epoch=0) for i in range(4)]
    seen = [(u.path, u.row_group) for s in shards for u in s]
    assert len(seen) == 16 and len(set(seen)) == 16  # disjoint cover
    assert all(len(s) == 4 for s in shards)
    # epoch varies the permutation but shard 0 of every process agrees
    again = shard_units(units, 0, 4, epoch=0)
    assert [(u.path, u.row_group) for u in again] == [
        (u.path, u.row_group) for u in shards[0]
    ]
    other_epoch = shard_units(units, 0, 4, epoch=1)
    assert [(u.path, u.row_group) for u in other_epoch] != [
        (u.path, u.row_group) for u in shards[0]
    ]


def test_queue_occupancy_tracks_results_queue(dataset):
    reader = ParquetShardReader(
        sorted(str(p) for p in dataset.glob("*.parquet")),
        batch_size=16, num_epochs=1, results_queue_size=4,
    )
    assert reader.queue_occupancy == 0  # not iterating yet
    it = iter(reader)
    next(it)
    # Workers run ahead of a stalled consumer up to the queue bound.
    import time

    deadline = time.monotonic() + 2.0
    while reader.queue_occupancy < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert 0 < reader.queue_occupancy <= 4
    reader.stop()


def test_single_epoch_reads_all_rows(dataset):
    with batch_loader(
        dataset, batch_size=32, num_epochs=1, workers_count=3, shuffle_row_groups=False
    ) as reader:
        ids = np.concatenate([b["id"] for b in reader])
    assert sorted(ids.tolist()) == list(range(256))


def test_batches_are_fixed_shape_and_drop_last(dataset):
    with batch_loader(dataset, batch_size=48, num_epochs=1) as reader:
        batches = list(reader)
    # 256 // 48 = 5 full batches; remainder 16 dropped
    assert len(batches) == 5
    assert all(len(b["id"]) == 48 for b in batches)


def test_keep_last_partial_batch(dataset):
    with batch_loader(dataset, batch_size=48, num_epochs=1, drop_last=False) as reader:
        batches = list(reader)
    assert [len(b["id"]) for b in batches] == [48] * 5 + [16]


def test_sharded_readers_are_disjoint(dataset):
    all_ids = []
    for shard in range(4):
        with batch_loader(
            dataset, batch_size=16, num_epochs=1, cur_shard=shard, shard_count=4
        ) as reader:
            all_ids += [b["id"] for b in reader]
    flat = np.concatenate(all_ids)
    assert sorted(flat.tolist()) == list(range(256))


def test_infinite_reader_crosses_epochs(dataset):
    with batch_loader(dataset, batch_size=100, num_epochs=None) as reader:
        it = iter(reader)
        got = sum(len(next(it)["id"]) for _ in range(5))
    assert got == 500  # > one 256-row epoch: reader kept going


def test_transform_spec_applied(dataset):
    spec = TransformSpec(
        func=lambda cols: {"twice": cols["value"] * 2},
        fields=[Field("twice", np.dtype(np.float32), ())],
    )
    with batch_loader(
        dataset, batch_size=64, num_epochs=1, transform_spec=spec, shuffle_row_groups=False
    ) as reader:
        b = next(iter(reader))
    assert set(b) == {"twice"}
    assert b["twice"].dtype == np.float32


def test_transform_spec_validates_schema(dataset):
    bad = TransformSpec(
        func=lambda cols: {"wrong_name": cols["value"]},
        fields=[Field("twice", np.dtype(np.float32), ())],
    )
    with pytest.raises(ValueError, match="declared"):
        with batch_loader(
            dataset, batch_size=8, num_epochs=1, transform_spec=bad,
            reader_pool_type="dummy",
        ) as reader:
            next(iter(reader))


def test_reader_from_delta_table(dataset, tmp_path):
    t = pa.table({"id": pa.array(np.arange(64))})
    write_delta(t, tmp_path / "dt", max_rows_per_file=16)
    with batch_loader(tmp_path / "dt", batch_size=16, num_epochs=1) as reader:
        ids = np.concatenate([b["id"] for b in reader])
    assert sorted(ids.tolist()) == list(range(64))


def test_too_many_shards_raises(dataset):
    with pytest.raises(ValueError, match="row groups"):
        ParquetShardReader(
            sorted(str(p) for p in dataset.glob("*.parquet")),
            batch_size=4,
            shard_count=64,
        )


def test_memory_estimate(dataset):
    reader = make_batch_reader(
        dataset, batch_size=4, workers_count=2, results_queue_size=20, num_epochs=1
    )
    # (2 workers + 20 queue slots) × 16 rows/group × 100 B, and the ring
    # that batches are assembled into: its bound × 4 rows/batch × 100 B
    from dss_ml_at_scale_tpu.data.reader import _RING_BOUND

    assert reader.memory_estimate(row_size_bytes=100) == (
        22 * 16 * 100 + _RING_BOUND * 4 * 100
    )


def test_stop_unblocks_workers_quickly(dataset):
    reader = make_batch_reader(
        dataset, batch_size=8, num_epochs=None, workers_count=4, results_queue_size=2
    )
    it = iter(reader)
    next(it)  # spin up workers, queue fills
    reader.stop()
    assert all(not t.is_alive() for t in reader._threads)


def test_worker_exception_propagates_in_thread_pool(dataset):
    """A failing transform must raise, not end the stream silently."""
    from dss_ml_at_scale_tpu.data.transform import Field

    def boom(cols):
        raise OSError("decode failed")

    bad = TransformSpec(func=boom, fields=[Field("x", np.dtype(np.float32), ())])
    with pytest.raises(RuntimeError, match="worker failed"):
        with batch_loader(
            dataset, batch_size=8, num_epochs=None, transform_spec=bad,
            reader_pool_type="thread", workers_count=2,
        ) as reader:
            next(iter(reader))


# -- provenance, quarantine, corrupt-sample isolation (PR 4) -----------------

def _sorted_files(dataset):
    return sorted(str(p) for p in dataset.glob("*.parquet"))


def test_emit_provenance_tags_batches_with_exact_rows(dataset):
    from dss_ml_at_scale_tpu.resilience.rollback import PROVENANCE_KEY

    with batch_loader(
        _sorted_files(dataset), batch_size=24, num_epochs=1,
        shuffle_row_groups=False, reader_pool_type="dummy",
        emit_provenance=True,
    ) as reader:
        batches = list(reader)
    for b in batches:
        prov = b[PROVENANCE_KEY]
        assert sum(r.num_rows for r in prov) == len(b["id"])
    # File order + dummy pool: batch 0 is rows [0,16) of rg0 + [0,8) of
    # rg1 of the first file — provenance must say exactly that.
    first = batches[0][PROVENANCE_KEY]
    assert [(r.row_group, r.row_lo, r.row_hi) for r in first] == [
        (0, 0, 16), (1, 0, 8),
    ]


def test_quarantined_rows_are_excluded_exactly(dataset, tmp_path):
    """Reader-level exclusion repacks the surviving stream: the batches
    equal a trainer-side skip of the same rows — the mechanism behind
    deterministic rollback parity."""
    from dss_ml_at_scale_tpu.resilience.rollback import (
        PROVENANCE_KEY,
        QuarantineList,
    )

    kwargs = dict(
        batch_size=16, num_epochs=1, shuffle_row_groups=False,
        reader_pool_type="dummy",
    )
    with batch_loader(
        _sorted_files(dataset), emit_provenance=True, **kwargs
    ) as reader:
        batches = list(reader)
    poison = batches[2]
    q = QuarantineList(tmp_path / "q.jsonl")
    q.add(poison[PROVENANCE_KEY], reason="chaos", step=3)

    with batch_loader(
        _sorted_files(dataset), quarantine=q, **kwargs
    ) as reader:
        excluded = [b["id"] for b in reader]
    skipped = [b["id"] for i, b in enumerate(batches) if i != 2]
    assert len(excluded) == len(skipped)
    for a, b in zip(excluded, skipped):
        np.testing.assert_array_equal(a, b)


def test_corrupt_sample_quarantined_and_skipped(dataset, tmp_path):
    """on_corrupt="quarantine": a row whose transform raises is isolated,
    counted, blocklisted, and dropped — the reader thread survives."""
    from dss_ml_at_scale_tpu import telemetry
    from dss_ml_at_scale_tpu.data.transform import Field
    from dss_ml_at_scale_tpu.resilience.rollback import QuarantineList

    def decode(cols):
        if np.any(cols["id"] == 100):
            raise ValueError("bad row")
        return {"value": cols["value"].astype(np.float32)}

    spec = TransformSpec(
        func=decode, fields=[Field("value", np.dtype(np.float32), ())]
    )

    def counter_value():
        for m in telemetry.snapshot()["metrics"]:
            if m["name"] == "corrupt_samples_total":
                return m["value"]
        return 0.0

    before = counter_value()
    q = QuarantineList(tmp_path / "q.jsonl")
    with batch_loader(
        _sorted_files(dataset), batch_size=16, num_epochs=1,
        shuffle_row_groups=False, transform_spec=spec, workers_count=2,
        quarantine=q, on_corrupt="quarantine", drop_last=False,
    ) as reader:
        values = np.concatenate([b["value"] for b in reader])
    assert len(values) == 255  # row id=100 dropped
    assert 100.0 not in values
    assert counter_value() - before == 1
    assert len(q) == 1
    entry = q.entries[0]
    assert entry["row_hi"] - entry["row_lo"] == 1
    assert "undecodable" in entry["reason"]

    # Default on_corrupt="raise" preserves fail-fast semantics.
    with pytest.raises(RuntimeError, match="worker failed"):
        with batch_loader(
            _sorted_files(dataset), batch_size=16, num_epochs=1,
            transform_spec=spec, workers_count=2,
        ) as reader:
            list(reader)


def test_sample_corrupt_fault_site_truncates_bytes(tmp_path):
    """The sample.corrupt site: truncated payload bytes hit the real
    decode error path and end up quarantined, deterministically."""
    from dss_ml_at_scale_tpu.data.transform import Field
    from dss_ml_at_scale_tpu.resilience import FaultPlan, faults
    from dss_ml_at_scale_tpu.resilience.rollback import QuarantineList

    t = pa.table({
        "payload": pa.array([np.float64(i).tobytes() for i in range(32)],
                            type=pa.binary()),
    })
    path = tmp_path / "bytes.parquet"
    pq.write_table(t, path, row_group_size=16)

    spec = TransformSpec(
        func=lambda cols: {"value": np.array(
            [np.frombuffer(b, np.float64, count=1)[0] for b in cols["payload"]],
            np.float64,
        )},
        fields=[Field("value", np.dtype(np.float64), ())],
    )
    q = QuarantineList(tmp_path / "q.jsonl")
    faults.install(FaultPlan.parse("sample.corrupt=1"))
    try:
        with batch_loader(
            [str(path)], batch_size=16, num_epochs=1, drop_last=False,
            shuffle_row_groups=False, reader_pool_type="dummy",
            transform_spec=spec, quarantine=q, on_corrupt="quarantine",
        ) as reader:
            values = np.concatenate([b["value"] for b in reader])
    finally:
        faults.clear()
    # Row 0 of the first row group was truncated mid-payload and dropped.
    assert len(values) == 31 and 0.0 not in values
    assert len(q) == 1 and q.entries[0]["row_lo"] == 0


# -- batches assembled into recycled host buffers (PR 25) ---------------------

GROUP_ROWS = 16


@pytest.fixture(scope="module")
def ring_dataset(tmp_path_factory):
    """4 files × 4 row groups × 16 rows = 256 rows: a numeric column, a
    string column (object dtype once read), and through ``image_spec`` a
    fixed-shape float32 ``image`` made from ``id``."""
    root = tmp_path_factory.mktemp("ring")
    for f in range(4):
        ids = np.arange(f * 64, (f + 1) * 64)
        pq.write_table(
            pa.table({"id": pa.array(ids),
                      "name": pa.array([f"row-{i}" for i in ids])}),
            root / f"part-{f}.parquet", row_group_size=GROUP_ROWS,
        )
    return sorted(str(p) for p in root.glob("*.parquet"))


def image_spec():
    def decode(cols):
        ids = cols["id"].astype(np.float32)
        image = ids[:, None, None, None] + np.arange(
            8 * 8 * 3, dtype=np.float32).reshape(8, 8, 3) / 1000
        return {"image": image, "label": cols["id"].astype(np.int32),
                "name": cols["name"]}

    return TransformSpec(
        func=decode,
        fields=[Field("image", np.dtype(np.float32), (8, 8, 3)),
                Field("label", np.dtype(np.int32), ()),
                Field("name", np.dtype(object), ())],
    )


def ring_reader(paths, **kw):
    kw = {"num_epochs": 1, "shuffle_row_groups": False,
          "reader_pool_type": "dummy", "transform_spec": image_spec(), **kw}
    return ParquetShardReader(paths, **kw)


def buffer_counts():
    from dss_ml_at_scale_tpu import telemetry

    got = {"recycled": 0.0, "fresh": 0.0, "view": 0.0}
    for m in telemetry.snapshot()["metrics"]:
        if m["name"] == "reader_batch_buffers_total":
            got[m["labels"]["source"]] = m["value"]
    return got


def counted(before):
    return {k: v - before[k] for k, v in buffer_counts().items()}


def spans_groups(batch_index, batch_size, rows=float("inf")):
    """Whether that batch of a stream in file order lies in more than one
    row group; the last of ``rows`` may be a short tail."""
    lo = batch_index * batch_size
    return lo // GROUP_ROWS != (min(lo + batch_size, rows) - 1) // GROUP_ROWS


def _take_before_the_ring(buf, n):
    """``reader._take`` as it stood before batches were assembled into
    recycled buffers (PR 24), body unchanged: the oracle."""
    taken = {}
    prov = []
    need = n
    rest = []
    for group, path, row_group, orig_rows in buf:
        if need == 0:
            rest.append((group, path, row_group, orig_rows))
            continue
        rows = len(next(iter(group.values())))
        use = min(rows, need)
        for k, v in group.items():
            taken.setdefault(k, []).append(v[:use])
        prov.append((path, row_group, orig_rows[:use]))
        if use < rows:
            rest.append((
                {k: v[use:] for k, v in group.items()},
                path, row_group, orig_rows[use:],
            ))
        need -= use
    batch = {k: np.concatenate(v) if len(v) > 1 else v[0] for k, v in taken.items()}
    return batch, prov, rest, sum(len(next(iter(g.values()))) for g, *_ in rest)


def batches_before_the_ring(reader):
    """The reader's own loop over its own row groups, with the oracle in
    ``_take``'s place."""
    buf, buffered, out = [], 0, []

    def assemble(n):
        nonlocal buf, buffered
        batch, prov, buf, buffered = _take_before_the_ring(buf, n)
        out.append(reader._finish_batch(batch, prov))

    for (group, orig_rows), unit in reader._row_groups():
        buf.append((group, unit.path, unit.row_group, orig_rows))
        buffered += len(orig_rows)
        while buffered >= reader.batch_size:
            assemble(reader.batch_size)
    if buffered and not reader.drop_last:
        assemble(buffered)
    return out


def assert_same_batch(got, want):
    assert list(got) == list(want)
    for k in want:
        if isinstance(want[k], np.ndarray):
            assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape
            np.testing.assert_array_equal(got[k], want[k])
        else:
            assert got[k] == want[k]          # provenance: RowRanges


# 8: inside a group; 12: inside one or across two; 24: two; 40: three or
# four; 100: seven, and a 56-row tail that spans four
@pytest.mark.parametrize("drop_last", [True, False])
@pytest.mark.parametrize("emit_provenance", [False, True])
@pytest.mark.parametrize("batch_size", [8, 12, 24, 40, 100])
def test_ring_batches_equal_the_old_takes(
    ring_dataset, batch_size, emit_provenance, drop_last
):
    kw = dict(batch_size=batch_size, emit_provenance=emit_provenance,
              drop_last=drop_last)
    want = batches_before_the_ring(ring_reader(ring_dataset, **kw))
    assert len(want) == (256 // batch_size if drop_last
                         else -(-256 // batch_size))
    before = buffer_counts()
    n = 0
    # each batch is dropped before the next is asked for, so buffers are
    # taken again: a recycled batch reads what a fresh one read
    for n, got in enumerate(ring_reader(ring_dataset, **kw), 1):
        assert_same_batch(got, want[n - 1])
    assert n == len(want)
    multi = sum(spans_groups(i, batch_size, 256) for i in range(n))
    assert (multi == 0) == (batch_size == 8)
    # the loop's variable holds one batch while the next is assembled: two
    # buffers, each new once
    fresh = min(2, multi)
    assert counted(before) == {
        "recycled": multi - fresh, "fresh": fresh, "view": n - multi}


def test_held_batches_are_never_overwritten(ring_dataset):
    from dss_ml_at_scale_tpu.data.reader import _RING_BOUND

    n = 3 * _RING_BOUND
    before = buffer_counts()
    reader = ring_reader(ring_dataset, batch_size=24, num_epochs=None)
    held, copies = [], []
    for batch in reader:
        held.append(batch)
        copies.append({k: np.array(v) for k, v in batch.items()})
        if len(held) == n:
            break
    reader.stop()
    for batch, copy in zip(held, copies):
        assert_same_batch(batch, copy)
    multi = sum(spans_groups(i, 24) for i in range(n))
    # nothing came free, so nothing was recycled: the ring grew to its
    # bound and the batches past it got arrays of their own
    assert counted(before) == {"recycled": 0, "fresh": multi, "view": n - multi}
    assert multi > 2 * _RING_BOUND
    assert [len(v) for v in reader._ring._buffers.values()] == [_RING_BOUND] * 2
    owners = {id(b) for v in reader._ring._buffers.values() for b in v}
    pooled = [i for i, b in enumerate(held) if id(b["image"]) in owners]
    assert len(pooled) == _RING_BOUND


def test_dropped_batches_are_recycled(ring_dataset):
    before = buffer_counts()
    n = 0
    for n, batch in enumerate(
        ring_reader(ring_dataset, batch_size=24, num_epochs=3), 1
    ):
        assert batch["image"][0, 0, 0, 0] == batch["label"][0]
    assert n == 32
    multi = sum(spans_groups(i, 24) for i in range(n))
    seen = counted(before)
    # the loop's variable holds one batch while the next is assembled
    assert seen["fresh"] == 2 and seen["view"] == n - multi
    assert seen["recycled"] == multi - 2


def test_a_view_a_consumer_keeps_holds_its_buffer(ring_dataset):
    kept, copies = [], []
    for batch in ring_reader(ring_dataset, batch_size=24, num_epochs=3):
        kept.append(batch["image"][5:7, 0, 0])        # a view of a view
        copies.append(kept[-1].copy())
    for view, copy in zip(kept, copies):
        np.testing.assert_array_equal(view, copy)


def test_a_short_tail_batch_is_a_view_of_a_buffer(ring_dataset):
    reader = ring_reader(ring_dataset, batch_size=100, drop_last=False)
    batches = [{k: v for k, v in b.items()} for b in reader]
    tail = batches[-1]
    assert len(tail["label"]) == 56
    owners = {id(b) for b in reader._ring._buffers["image"]}
    assert id(tail["image"].base) in owners
    assert tail["label"].tolist() == list(range(200, 256))


def test_device_put_arrays_outlive_the_ring(ring_dataset):
    import jax

    from dss_ml_at_scale_tpu.data.reader import _RING_BOUND

    assert jax.devices()[0].platform == "cpu"
    placed, want = [], []
    reader = ring_reader(ring_dataset, batch_size=24, num_epochs=None)
    for batch in reader:
        want.append(int(batch["label"][0]))
        # the feeder's call: host arrays go in, the host batch is dropped
        placed.append(jax.device_put(
            {k: batch[k] for k in ("image", "label")}))
        if len(placed) > 3 * _RING_BOUND:       # wrapped more than twice
            break
    reader.stop()
    for first, on_device in zip(want, placed):
        image, label = np.asarray(on_device["image"]), np.asarray(on_device["label"])
        assert label.tolist() == [i % 256 for i in range(first, first + 24)]
        np.testing.assert_array_equal(image[:, 0, 0, 0], label.astype(np.float32))


def test_object_columns_and_one_group_batches_take_no_buffer(ring_dataset):
    before = buffer_counts()
    # strings only, every batch across two groups
    reader = ParquetShardReader(
        ring_dataset, batch_size=24, num_epochs=1, columns=["name"],
        shuffle_row_groups=False, reader_pool_type="dummy")
    names = [b["name"] for b in reader]
    assert all(a.dtype == object and len(a) == 24 for a in names)
    assert np.concatenate(names).tolist() == [f"row-{i}" for i in range(240)]
    assert reader._ring._buffers == {}
    # numeric, but each batch is one whole row group
    reader = ring_reader(ring_dataset, batch_size=GROUP_ROWS)
    assert len(list(reader)) == 16
    assert reader._ring._buffers == {}
    assert counted(before) == {"recycled": 0, "fresh": 0, "view": 26}


def test_a_column_whose_row_shape_changes_is_not_pooled(ring_dataset):
    from dss_ml_at_scale_tpu.data.reader import _BufferRing

    ring = _BufferRing(4, 2)
    a, b = np.ones((2, 3), np.float32), np.ones((2, 5), np.float32)
    out, source = ring.concatenate("x", [a, a])
    assert source == "fresh" and out.shape == (4, 3)
    del out
    out, source = ring.concatenate("x", [b, b])
    assert source is None and out.shape == (4, 5)
    out, source = ring.concatenate("x", [a, a.astype(np.float64)])
    assert source is None and out.dtype == np.float64
    out, source = ring.concatenate("x", [a, a[:1]])
    assert source == "recycled" and out.shape == (3, 3) and out.base is not None


def test_batches_held_by_another_thread_stay_whole(ring_dataset):
    """The feeder's shape: one thread pulls batches, another holds each a
    little longer than the next pull. Nothing held is written to, and the
    ring still recycles."""
    import queue
    import sys
    import threading
    import time

    handed: queue.Queue = queue.Queue(maxsize=3)
    wrong: list = []

    def hold_and_check():
        while True:
            item = handed.get()
            if item is None:
                return
            batch, labels_then = item
            time.sleep(0.0005)
            if not (
                np.array_equal(batch["label"], labels_then)
                and np.array_equal(batch["image"][:, 0, 0, 0],
                                   labels_then.astype(np.float32))
            ):
                wrong.append(labels_then[0])

    before = buffer_counts()
    checker = threading.Thread(target=hold_and_check, daemon=True)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        checker.start()
        reader = ring_reader(
            ring_dataset, batch_size=24, num_epochs=None,
            reader_pool_type="thread", workers_count=4)
        deadline = time.monotonic() + 20
        for n, batch in enumerate(reader, 1):
            handed.put((batch, batch["label"].copy()), timeout=10)
            if n == 400 or time.monotonic() > deadline:
                break
        reader.stop()
        handed.put(None, timeout=10)
        checker.join(timeout=10)
    finally:
        sys.setswitchinterval(interval)
    assert not checker.is_alive() and n == 400
    assert wrong == []
    seen = counted(before)
    # the queue, both threads' hands and the one being filled are about
    # the bound: a checker that falls behind costs a fresh array, no more
    assert seen["view"] == 0 and seen["recycled"] + seen["fresh"] == 400
    assert seen["recycled"] > 200
