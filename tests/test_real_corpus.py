"""Real-corpus drop-in battery (ISSUE 10).

Pins the whole ingested-trace path end to end:

* **golden end-to-end** — the checked-in fixtures
  (``tests/fixtures/msr_tiny.csv``, ``raw_tiny.raw``) ingest into a
  corpus directory whose manifest, fingerprint and sweep hit
  ratios match frozen values (regenerate deliberately, never silently);
* **round-trip differential** — the synthetic quick registry exported
  to npz volumes and re-ingested through :class:`RealCorpus` must
  reproduce the synthetic suite bit-identically: same names/lengths,
  same lane schedule, same hit curves, zero extra compiles;
* **ingestion fuzz battery** — malformed MSR rows and raw records
  (truncated rows, non-integer fields, non-monotonic timestamps,
  zero-length ranges, negative offsets, uint64 overflow, torn trailing
  records) raise clear ``ValueError``s naming the file, never crash or
  silently truncate — plus property tests that every *valid* input
  ingests to exactly the block expansion the format promises;
* **family / degenerate surfacing** — ``family_of`` fallbacks classify
  ingested volumes, ``workload_stats`` stays total on len<=1 traces,
  and the figure engine's by-family rows surface an ``ingested`` family
  instead of dropping the rows.
"""

import json
import os
import pathlib
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.cache import SimConfig, sweep_streaming
from repro.core import MithrilConfig
from repro.traces import (INGESTED, RealCorpus, build_corpus,
                          corpus_fingerprint, corpus_specs, family_of,
                          ingest_msr_csv, ingest_raw, ingest_to_dir,
                          load_corpus_dir, read_manifest, resolve_corpus_dir,
                          scan_corpus_dir, stack_padded, workload_stats,
                          write_corpus_dir)

from benchmarks import corpus_figures as cf

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
MSR = os.path.join(FIXTURES, "msr_tiny.csv")
RAW = os.path.join(FIXTURES, "raw_tiny.raw")

# small mining tables: the fixtures hold ~20-30 distinct blocks, so the
# paper-suite mine_rows=64 threshold would never trigger on them
MCFG = MithrilConfig(min_support=2, max_support=8, lookahead=40,
                     rec_buckets=512, rec_ways=4, mine_rows=8,
                     pf_buckets=512, pf_ways=4, prefetch_list=3)

# ---- frozen goldens: regenerate with the recipe in each test ----------
GOLDEN_FP = "708ae948"
GOLDEN_LENGTHS = (66, 57)
GOLDEN_HR = {
    "lru": (0.363636, 0.0),
    "mithril-lru": (0.363636, 0.245614),
}


@pytest.fixture(scope="module")
def fixture_corpus(tmp_path_factory):
    """The checked-in fixtures ingested into a corpus directory."""
    d = tmp_path_factory.mktemp("fixture_corpus")
    ingest_to_dir({"msr_tiny": MSR, "raw_tiny": RAW}, str(d))
    return str(d)


@pytest.fixture()
def engine_reset():
    cf.reset_engine()
    yield
    cf.reset_engine()


class TestGoldenEndToEnd:
    """ingest -> npz+manifest -> RealCorpus -> sweep == frozen values."""

    def test_manifest_and_fingerprint(self, fixture_corpus):
        man = read_manifest(fixture_corpus)
        assert man["version"] == 1
        assert man["fingerprint"] == GOLDEN_FP
        vols = man["volumes"]
        assert [v["name"] for v in vols] == ["msr_tiny", "raw_tiny"]
        assert tuple(v["requests"] for v in vols) == GOLDEN_LENGTHS
        assert all(v["family"] == INGESTED for v in vols)
        # stats are frozen structure, not just presence
        assert vols[0]["stats"]["unique_blocks"] == 30
        assert vols[1]["stats"]["unique_blocks"] == 21
        assert not vols[0]["stats"]["degenerate"]

    def test_frozen_hit_ratios(self, fixture_corpus):
        rc = RealCorpus(fixture_corpus)
        assert rc.fingerprint() == GOLDEN_FP
        names, blocks, lengths = rc.suite()
        assert names == ("msr_tiny", "raw_tiny")
        assert tuple(int(x) for x in lengths) == GOLDEN_LENGTHS
        grid = {"lru": SimConfig(capacity=8),
                "mithril-lru": SimConfig(capacity=8, use_mithril=True,
                                         mithril=MCFG)}
        for cname, cfg in grid.items():
            res = sweep_streaming(cfg, blocks, lengths).result
            got = tuple(round(float(h), 6) for h in res.hit_ratios())
            assert got == GOLDEN_HR[cname], cname
        # the prefetcher's win on the looping raw volume is the whole
        # point of the fixture: LRU scores zero on a loop bigger than
        # the cache, MITHRIL's mined associations recover hits
        assert GOLDEN_HR["mithril-lru"][1] > GOLDEN_HR["lru"][1]

    def test_cli_ingest_matches_api(self, tmp_path, capsys):
        from repro.traces import io as trace_io
        fp = trace_io.main([str(tmp_path / "c"), MSR, RAW])
        assert fp == GOLDEN_FP
        out = capsys.readouterr().out
        assert "2 volume(s)" in out and GOLDEN_FP in out


class TestRoundTripDifferential:
    """Synthetic quick corpus -> npz dir -> RealCorpus: bit-identical."""

    TLEN = 300

    @pytest.fixture(scope="class")
    def exported(self, tmp_path_factory):
        d = tmp_path_factory.mktemp("synthetic_export")
        traces = build_corpus(corpus_specs(self.TLEN, "quick"))
        fams = {n: family_of(n) for n in traces}
        write_corpus_dir(str(d), traces, fams)
        return str(d), traces, fams

    def test_suite_is_bit_identical(self, exported):
        d, traces, fams = exported
        rc = RealCorpus(d)
        names_s, blocks_s, lengths_s = stack_padded(traces)
        names_r, blocks_r, lengths_r = rc.suite("full")
        assert tuple(names_r) == tuple(names_s)
        assert np.array_equal(lengths_r, lengths_s)
        assert np.array_equal(blocks_r, blocks_s)
        # manifest families round-trip (no INGESTED fallback needed)
        assert all(rc.family(n) == fams[n] for n in names_r)
        # content hash agrees with hashing the in-memory dict
        assert rc.fingerprint("full") == corpus_fingerprint(traces)

    def test_nested_scales_subset_identically(self, exported):
        d, traces, _ = exported
        rc = RealCorpus(d)
        # quick-of-quick is the identity sample; a mid request on a
        # 16-volume corpus caps at the volume count
        assert rc.subset_names("quick") == tuple(traces)
        assert rc.subset_names("mid") == tuple(traces)
        with pytest.raises(ValueError, match="scale"):
            rc.subset_names("huge")

    def test_sweeps_and_packer_bit_identical(self, exported):
        d, traces, _ = exported
        names_s, blocks_s, lengths_s = stack_padded(traces)
        _, blocks_r, lengths_r = RealCorpus(d).suite("full")
        assert np.array_equal(lengths_s, lengths_r)
        cfg = SimConfig(capacity=64, use_mithril=True, mithril=MCFG)
        out_s = sweep_streaming(cfg, blocks_s, lengths_s)
        out_r = sweep_streaming(cfg, blocks_r, lengths_r)

        def schedule(out):      # the stage timings are noise, not schedule
            return {k: v for k, v in out.streaming_stats().items()
                    if k != "pipeline"}

        assert schedule(out_s) == schedule(out_r)
        res_s, res_r = out_s.result, out_r.result
        assert np.array_equal(res_s.hit_curve, res_r.hit_curve)
        assert np.array_equal(res_s.hit_ratios(), res_r.hit_ratios())
        # same geometry + same config -> the jit cache is warm: the
        # re-ingested corpus must not cost a single extra compile
        assert res_r.compiles == 0

    def test_length_cap_is_noop_at_full_length(self, exported):
        d, traces, _ = exported
        rc = RealCorpus(d)
        capped = rc.suite("full", self.TLEN)
        uncapped = rc.suite("full")
        assert np.array_equal(capped[1], uncapped[1])
        short = rc.suite("full", 50)
        assert int(np.max(short[2])) <= 50


class TestCorpusRunEngine:
    """The figure engine's drop-in seam: tagged jobs, families, caps."""

    def test_real_corpus_run(self, fixture_corpus, engine_reset):
        run = cf.corpus_run("quick", 300, corpus_dir=fixture_corpus)
        assert list(run.names) == ["msr_tiny", "raw_tiny"]
        assert run.fingerprint == GOLDEN_FP
        assert run.corpus == GOLDEN_FP
        assert run.job == f"corpus_figures_quick@{GOLDEN_FP}"
        assert run.job_name("corpus_quick") == f"corpus_quick@{GOLDEN_FP}"
        assert all(f == INGESTED for f in run.families)
        assert not run.degenerate.any()

    def test_synthetic_default_untagged(self, engine_reset):
        run = cf.corpus_run("quick", 300)
        assert run.fingerprint is None
        assert run.corpus == "synthetic"
        assert run.job == "corpus_figures_quick"
        assert run.job_name("corpus_quick") == "corpus_quick"

    def test_trace_len_caps_real_traces(self, fixture_corpus,
                                        engine_reset):
        run = cf.corpus_run("quick", 40, corpus_dir=fixture_corpus)
        assert int(np.max(run.lengths)) <= 40
        # distinct cap -> distinct fingerprint -> distinct job key
        full = cf.corpus_run("quick", 300, corpus_dir=fixture_corpus)
        assert run.fingerprint != full.fingerprint
        assert run.job != full.job

    def test_env_var_resolution(self, fixture_corpus, monkeypatch,
                                engine_reset):
        monkeypatch.setenv("REPRO_CORPUS_DIR", fixture_corpus)
        assert resolve_corpus_dir(None) == fixture_corpus
        assert resolve_corpus_dir("/explicit/wins") == "/explicit/wins"
        run = cf.corpus_run("quick", 300)
        assert run.fingerprint == GOLDEN_FP
        monkeypatch.delenv("REPRO_CORPUS_DIR")
        assert resolve_corpus_dir(None) is None

    def test_engine_golden_hit_ratio(self, fixture_corpus, engine_reset):
        # the full engine path (CorpusRun.result -> record_sweep) on the
        # fixtures at the benchmark capacity: everything fits, so both
        # volumes score their reuse fraction exactly
        run = cf.corpus_run("quick", 300, corpus_dir=fixture_corpus)
        hr = run.hit_ratios(["lru"])["lru"]
        assert tuple(round(float(h), 6) for h in hr) == \
            (0.545455, 0.631579)


class TestFamilySurfacing:
    """family_of fallbacks + by-family rows keep ingested traces."""

    def test_family_of_fallback(self):
        with pytest.raises(ValueError, match="registry"):
            family_of("web2")
        assert family_of("web2", INGESTED) == INGESTED
        assert family_of("seq012", INGESTED) == "seq"
        assert family_of("vol123", "custom") == "custom"

    def test_family_rows_surface_ingested(self):
        fams = np.array(["seq", INGESTED, INGESTED])
        rows = cf.family_rows(fams, {"hr": np.array([0.5, 0.2, 0.4])})
        assert [r[0] for r in rows] == ["seq", INGESTED, "all"]
        ingested_row = rows[1]
        assert ingested_row[1] == 2
        assert ingested_row[2] == pytest.approx(0.3)

    def test_family_rows_extra_families_sorted(self):
        fams = np.array(["zzz", "aaa", "seq"])
        rows = cf.family_rows(fams, {"v": np.arange(3.0)})
        assert [r[0] for r in rows] == ["seq", "aaa", "zzz", "all"]

    def test_workload_stats_total_on_degenerate(self):
        empty = workload_stats(np.array([], np.int32))
        assert empty["degenerate"] and empty["requests"] == 0
        one = workload_stats(np.array([7], np.int32))
        assert one["degenerate"] and one["sequential_fraction"] == 0.0
        real = workload_stats(ingest_raw(RAW))
        assert not real["degenerate"]
        assert real["requests"] == GOLDEN_LENGTHS[1]

    def test_degenerate_volume_surfaces_through_engine(
            self, tmp_path, engine_reset):
        write_corpus_dir(str(tmp_path), {
            "one": np.array([5], np.int32),
            "loop": np.tile(np.arange(20, dtype=np.int32), 10),
        })
        run = cf.corpus_run("quick", 300, corpus_dir=str(tmp_path))
        flags = dict(zip(run.names, run.degenerate))
        assert flags["one"] and not flags["loop"]


class TestCompareCorpusGeometry:
    """compare.py treats the corpus fingerprint as a geometry key."""

    @staticmethod
    def _doc(corpus=None):
        meta = {"suite": "quick", "quick": True, "trace_len": 100,
                "corpus_scale": "quick", "corpus_len": 300,
                "n_devices": 1}
        if corpus is not None:
            meta["corpus"] = corpus
        sweep = {"job": "corpus_quick", "config": "lru", "label": "lru",
                 "n_traces": 2, "hit_ratios": [0.5, 0.6],
                 "hit_ratio_mean": 0.55, "precision_mean": None,
                 "seconds": 1.0, "compiles": 1}
        return {"meta": meta, "jobs": [], "sweeps": [sweep]}

    def test_same_corpus_is_comparable(self):
        from benchmarks.compare import compare
        f, w, n, compared = compare(self._doc("abc123"),
                                    self._doc("abc123"), 0.2)
        assert compared == 1 and not f

    def test_real_vs_synthetic_skips(self):
        from benchmarks.compare import compare
        f, w, notes, compared = compare(self._doc("abc123"),
                                        self._doc(None), 0.2)
        assert compared == 0 and not f
        assert any("geometry differs" in x for x in notes)

    def test_missing_key_defaults_to_synthetic(self):
        # a pre-ISSUE-10 baseline (no "corpus" meta) still compares
        # against a fresh synthetic run — the default must not skip
        from benchmarks.compare import compare
        f, w, n, compared = compare(self._doc("synthetic"),
                                    self._doc(None), 0.2)
        assert compared == 1 and not f

    def test_distinct_fingerprints_skip(self):
        from benchmarks.compare import compare
        f, w, notes, compared = compare(self._doc("abc123"),
                                        self._doc("def456"), 0.2)
        assert compared == 0 and not f


class TestMsrValidation:
    """Malformed MSR rows raise file:line ValueErrors, never truncate."""

    def _write(self, tmp_path, rows):
        p = tmp_path / "t.csv"
        p.write_text("Timestamp,Hostname,DiskNumber,Type,Offset,Size,"
                     "ResponseTime\n" + "\n".join(rows) + "\n")
        return str(p)

    def test_truncated_row(self, tmp_path):
        p = self._write(tmp_path, ["1,h,0,Read,4096,4096,1",
                                   "2,h,0,Read"])
        with pytest.raises(ValueError, match=r"t\.csv:3.*truncated"):
            ingest_msr_csv(p)

    def test_non_integer_field(self, tmp_path):
        p = self._write(tmp_path, ["1,h,0,Read,40x96,4096,1"])
        with pytest.raises(ValueError, match="non-integer"):
            ingest_msr_csv(p)

    def test_non_monotonic_timestamp(self, tmp_path):
        p = self._write(tmp_path, ["5,h,0,Read,0,4096,1",
                                   "4,h,0,Read,4096,4096,1"])
        with pytest.raises(ValueError, match="non-monotonic"):
            ingest_msr_csv(p)

    def test_zero_length_range(self, tmp_path):
        p = self._write(tmp_path, ["1,h,0,Read,4096,0,1"])
        with pytest.raises(ValueError, match="zero-length"):
            ingest_msr_csv(p)

    def test_negative_offset(self, tmp_path):
        p = self._write(tmp_path, ["1,h,0,Read,-4096,4096,1"])
        with pytest.raises(ValueError, match="negative byte offset"):
            ingest_msr_csv(p)

    def test_int64_overflow_range(self, tmp_path):
        huge = 2**63 - 10
        p = self._write(tmp_path, [f"1,h,0,Read,{huge},4096,1"])
        with pytest.raises(ValueError, match="overflows int64"):
            ingest_msr_csv(p)

    def test_monotonicity_covers_filtered_rows(self, tmp_path):
        # a Write row with a decreasing timestamp must still raise even
        # when only="Read" filters it out of the block stream
        p = self._write(tmp_path, ["5,h,0,Read,0,4096,1",
                                   "3,h,0,Write,4096,4096,1"])
        with pytest.raises(ValueError, match="non-monotonic"):
            ingest_msr_csv(p, only="Read")

    def test_type_filter_and_expansion(self, tmp_path):
        p = self._write(tmp_path, ["1,h,0,Read,0,8192,1",
                                   "2,h,0,Write,40960,4096,1",
                                   "3,h,0,Read,12288,4096,1"])
        got = ingest_msr_csv(p, only="Read", rebase=False)
        assert got.tolist() == [0, 1, 3]

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 2**40),
                              st.integers(1, 5 * 4096)),
                    min_size=1, max_size=30))
    def test_valid_rows_expand_exactly(self, reqs):
        # no pytest fixtures here: @given-wrapped tests fill every
        # parameter from strategies (the fallback shim requires it)
        rows = [f"{i},h,0,Read,{off},{size},1"
                for i, (off, size) in enumerate(reqs)]
        with tempfile.TemporaryDirectory() as d:
            p = self._write(pathlib.Path(d), rows)
            got = ingest_msr_csv(p, rebase=False)
        expect = []
        for off, size in reqs:
            first, last = off // 4096, (off + size - 1) // 4096
            expect.extend(range(first, last + 1))
        assert got.tolist() == expect


class TestRawValidation:
    """Raw records: overflow + torn-record rejection, exact decode."""

    def test_uint64_overflow(self, tmp_path):
        p = tmp_path / "t.raw"
        np.array([2**63 + 5, 4096], dtype="<u8").tofile(p)
        with pytest.raises(ValueError, match="overflows signed int64"):
            ingest_raw(str(p))

    def test_torn_trailing_record(self, tmp_path):
        p = tmp_path / "t.raw"
        p.write_bytes(np.array([0, 4096], dtype="<u8").tobytes() + b"abc")
        with pytest.raises(ValueError, match="trailing 3 bytes"):
            ingest_raw(str(p))

    def test_empty_file(self, tmp_path):
        p = tmp_path / "t.raw"
        p.write_bytes(b"")
        assert ingest_raw(str(p)).size == 0

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.integers(0, 2**62), min_size=0, max_size=64))
    def test_decode_matches_numpy(self, offs):
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "t.raw")
            np.asarray(offs, dtype="<u8").tofile(p)
            got = ingest_raw(p, rebase=False)
        assert got.tolist() == [o // 4096 for o in offs]

    def test_chunk_boundary_preserves_records(self, tmp_path):
        # tiny chunk_bytes forces mid-record chunk splits: the carry
        # logic must keep every record in phase
        p = tmp_path / "t.raw"
        offs = np.arange(100, dtype="<u8") * 4096
        offs.tofile(p)
        got = ingest_raw(str(p), rebase=False, chunk_bytes=13)
        assert got.tolist() == list(range(100))


class TestCorpusDirValidation:
    """scan/load reject stale manifests and malformed directories."""

    def _corpus(self, d):
        write_corpus_dir(str(d), {"a": np.arange(5, dtype=np.int32),
                                  "b": np.arange(3, dtype=np.int32)})

    def test_stale_manifest_requests(self, tmp_path):
        self._corpus(tmp_path)
        man = read_manifest(str(tmp_path))
        man["volumes"][0]["requests"] = 999
        (tmp_path / "manifest.json").write_text(json.dumps(man))
        with pytest.raises(ValueError, match="manifest requests"):
            load_corpus_dir(str(tmp_path))

    def test_manifest_references_missing_file(self, tmp_path):
        self._corpus(tmp_path)
        os.remove(tmp_path / "a.npz")
        with pytest.raises(ValueError, match="missing file"):
            scan_corpus_dir(str(tmp_path))

    def test_duplicate_volume_name(self, tmp_path):
        self._corpus(tmp_path)
        man = read_manifest(str(tmp_path))
        man["volumes"].append(dict(man["volumes"][0]))
        (tmp_path / "manifest.json").write_text(json.dumps(man))
        with pytest.raises(ValueError, match="duplicate"):
            scan_corpus_dir(str(tmp_path))

    def test_invalid_manifest_json(self, tmp_path):
        self._corpus(tmp_path)
        (tmp_path / "manifest.json").write_text("{nope")
        with pytest.raises(ValueError, match="not valid json"):
            scan_corpus_dir(str(tmp_path))

    def test_manifestless_discovery(self, tmp_path):
        self._corpus(tmp_path)
        os.remove(tmp_path / "manifest.json")
        entries = scan_corpus_dir(str(tmp_path))
        assert [e["name"] for e in entries] == ["a", "b"]
        assert all(e["family"] == INGESTED for e in entries)
        traces, fams = load_corpus_dir(str(tmp_path))
        assert list(traces) == ["a", "b"]
        assert fams == {"a": INGESTED, "b": INGESTED}

    def test_empty_directory_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="not a corpus directory"):
            scan_corpus_dir(str(tmp_path))
        with pytest.raises(ValueError, match="not a corpus directory"):
            scan_corpus_dir(str(tmp_path / "absent"))
