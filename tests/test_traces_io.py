"""Trace io: canonical round-trips, ingestion, total workload stats.

ISSUE 4 satellites: ``workload_stats`` must be a total function (no
NaN/crash on length-<=1 traces), ``save_traces`` must raise on block ids
the canonical int32 form cannot hold (instead of silently truncating),
and the MSR-CSV / raw ingesters must land bit-identical block streams in
the canonical npz.
"""

import os

import numpy as np
import pytest

from repro.traces import (ingest, ingest_msr_csv, ingest_raw, ingest_to_npz,
                          load_traces, mixed, save_traces, workload_stats)


class TestRoundTrip:
    def test_save_load_bit_identical(self, tmp_path):
        traces = {f"v{i}": mixed(800, 0.3, 0.4, 0.3, seed=i)
                  for i in range(3)}
        path = os.path.join(tmp_path, "suite.npz")
        save_traces(path, traces)
        back = load_traces(path)
        assert set(back) == set(traces)
        for k in traces:
            assert back[k].dtype == np.int32
            np.testing.assert_array_equal(back[k], traces[k], err_msg=k)

    def test_stats_stable_across_round_trip(self, tmp_path):
        tr = mixed(600, 0.5, 0.3, 0.2, seed=9)
        path = os.path.join(tmp_path, "one.npz")
        save_traces(path, {"t": tr})
        assert workload_stats(load_traces(path)["t"]) == workload_stats(tr)

    def test_save_rejects_out_of_range_ids(self, tmp_path):
        path = os.path.join(tmp_path, "bad.npz")
        with pytest.raises(ValueError, match="int32"):
            save_traces(path, {"big": np.array([0, 2 ** 31], np.int64)})
        with pytest.raises(ValueError, match="int32"):
            save_traces(path, {"neg": np.array([-2], np.int64)})
        assert not os.path.exists(path)   # nothing half-written

    def test_save_accepts_int32_boundary(self, tmp_path):
        path = os.path.join(tmp_path, "edge.npz")
        save_traces(path, {"edge": np.array([0, 2 ** 31 - 1], np.int64)})
        np.testing.assert_array_equal(load_traces(path)["edge"],
                                      [0, 2 ** 31 - 1])


class TestWorkloadStats:
    def test_total_on_degenerate_traces(self):
        """Length-0/1 traces: well-defined zeros, never NaN (np.mean over
        an empty np.diff used to warn and return NaN)."""
        for tr in (np.array([], np.int32), np.array([7], np.int32)):
            with np.errstate(all="raise"):
                stats = workload_stats(tr)
            assert stats["requests"] == len(tr)
            assert stats["sequential_fraction"] == 0.0
            for v in stats.values():
                assert np.isfinite(v), (len(tr), stats)

    def test_sequential_fraction(self):
        assert workload_stats(np.arange(100))["sequential_fraction"] == 1.0
        st = workload_stats(np.zeros(100, np.int64))
        assert st["sequential_fraction"] == 0.0
        assert st["unique_blocks"] == 1 and st["mean_freq"] == 100.0


class TestIngest:
    def _write_msr(self, path, records):
        with open(path, "w") as f:
            f.write("Timestamp,Hostname,DiskNumber,Type,Offset,Size,"
                    "ResponseTime\n")
            for i, (typ, off, size) in enumerate(records):
                f.write(f"{128166372003061629 + i},src1,0,{typ},{off},"
                        f"{size},{1000 + i}\n")

    def test_msr_csv_block_expansion(self, tmp_path):
        path = os.path.join(tmp_path, "vol.csv")
        # 4KB at block 2, 8KB spanning blocks 5..6, unaligned tail 3..4
        self._write_msr(path, [("Read", 8192, 4096),
                               ("Write", 20480, 8192),
                               ("Read", 12800, 4096)])
        got = ingest_msr_csv(path, block_size=4096, rebase=False)
        np.testing.assert_array_equal(got, [2, 5, 6, 3, 4])

    def test_msr_csv_type_filter_and_rebase(self, tmp_path):
        path = os.path.join(tmp_path, "vol.csv")
        self._write_msr(path, [("Read", 40960, 4096),
                               ("Write", 8192, 4096),
                               ("read", 45056, 4096)])
        got = ingest_msr_csv(path, block_size=4096, only="Read")
        np.testing.assert_array_equal(got, [0, 1])   # rebased, writes out

    def test_msr_csv_write_column(self, tmp_path):
        """``writes=True``: one flag per expanded block, set on every
        block of a Write record, through the filter and the rebase; the
        blocks are the default return's."""
        path = os.path.join(tmp_path, "vol.csv")
        self._write_msr(path, [("Read", 8192, 4096),
                               ("Write", 20480, 8192),
                               ("Read", 12800, 4096),
                               ("write", 40960, 4096)])
        blocks, writes = ingest_msr_csv(path, block_size=4096,
                                        rebase=False, writes=True)
        np.testing.assert_array_equal(blocks, [2, 5, 6, 3, 4, 10])
        np.testing.assert_array_equal(writes, [0, 1, 1, 0, 0, 1])
        assert writes.dtype == bool
        np.testing.assert_array_equal(
            ingest_msr_csv(path, block_size=4096, rebase=False), blocks)
        blocks, writes = ingest_msr_csv(path, block_size=4096,
                                        only="Write", writes=True)
        np.testing.assert_array_equal(blocks, [0, 1, 5])    # rebased
        assert writes.all()
        blocks, writes = ingest_msr_csv(path, block_size=4096, only="Read",
                                        chunk_rows=1, writes=True)
        np.testing.assert_array_equal(blocks, [0, 1, 2])
        assert not writes.any()

    def test_msr_fixture_write_column(self):
        path = os.path.join(os.path.dirname(__file__), "fixtures",
                            "msr_tiny.csv")
        blocks, writes = ingest_msr_csv(path, writes=True)
        np.testing.assert_array_equal(blocks, ingest_msr_csv(path))
        assert writes.shape == blocks.shape
        # the file's four 4 KB writes, one block each
        assert int(writes.sum()) == 4
        reads = ingest_msr_csv(path, only="Read", rebase=False)
        assert len(reads) == len(blocks) - 4

    def test_msr_csv_streams_in_chunks(self, tmp_path):
        path = os.path.join(tmp_path, "big.csv")
        offs = np.arange(500) * 4096
        self._write_msr(path, [("Read", int(o), 4096) for o in offs])
        one = ingest_msr_csv(path, block_size=4096, rebase=False)
        tiny = ingest_msr_csv(path, block_size=4096, rebase=False,
                              chunk_rows=7)
        np.testing.assert_array_equal(one, np.arange(500))
        np.testing.assert_array_equal(tiny, one)

    def test_raw_round_trip(self, tmp_path):
        path = os.path.join(tmp_path, "vol.raw")
        blocks = np.array([5, 6, 7, 3, 5, 100], np.int64)
        (blocks.astype("<u8") * 4096).tofile(path)
        got = ingest_raw(path, block_size=4096, rebase=False)
        np.testing.assert_array_equal(got, blocks)
        # chunk sizes that never align with the 8-byte record boundary:
        # the partial record must carry into the next chunk, not shift
        # every later offset out of phase
        for chunk_bytes in (16, 10, 7, 3):
            got = ingest_raw(path, block_size=4096, rebase=False,
                             chunk_bytes=chunk_bytes)
            np.testing.assert_array_equal(got, blocks,
                                          err_msg=f"chunk={chunk_bytes}")

    def test_raw_rejects_torn_file(self, tmp_path):
        path = os.path.join(tmp_path, "torn.raw")
        with open(path, "wb") as f:
            f.write(np.array([4096], "<u8").tobytes() + b"\x01\x02\x03")
        with pytest.raises(ValueError, match="trailing"):
            ingest_raw(path, block_size=4096)

    def test_ingest_dispatch(self, tmp_path):
        csv = os.path.join(tmp_path, "a.csv")
        raw = os.path.join(tmp_path, "b.raw")
        self._write_msr(csv, [("Read", 4096, 4096)])
        np.array([4096], "<u8").tofile(raw)
        np.testing.assert_array_equal(ingest(csv, rebase=False), [1])
        np.testing.assert_array_equal(ingest(raw, rebase=False), [1])
        with pytest.raises(ValueError, match="format"):
            ingest(raw, fmt="vhs")

    def test_ingest_to_npz_end_to_end(self, tmp_path):
        """Files -> canonical npz -> load: bit-identical blocks, stats
        summaries per volume."""
        csv = os.path.join(tmp_path, "web2.csv")
        self._write_msr(csv, [("Read", 4096 * b, 4096)
                              for b in (9, 10, 11, 4, 9)])
        out = os.path.join(tmp_path, "corpus.npz")
        stats = ingest_to_npz({"web2": csv}, out)
        assert stats["web2"]["requests"] == 5
        assert stats["web2"]["unique_blocks"] == 4
        back = load_traces(out)
        np.testing.assert_array_equal(back["web2"], [5, 6, 7, 0, 5])
        assert workload_stats(back["web2"]) == stats["web2"]
