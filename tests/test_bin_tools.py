"""Scripted checks for the bin/ + bench provenance fixes (ISSUE 1
satellites; ADVICE findings).

- ``bin/summarize_onchip.py``: A/B ranking must read each stage's OWN
  config row from the headline's nested matrix (the top-level headline
  value is the stale bert_base number on subset runs) and must not
  declare a winner on an all-equal group (string tie-break regression).
- ``bin/run_onchip_suite.sh``: a second suite refuses to run while one
  holds the lock, with the distinctive exit code 75.
- ``bench.py``: the outlier re-probe records the DISCARDED reading
  (never a duplicate of the kept one), and HETU_BENCH_FORCE_FLASH
  stamps ``flash_forced`` provenance into the result row.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_stage(logdir, name, headline):
    with open(os.path.join(logdir, name + ".log"), "w") as f:
        f.write("noise line\n")
        f.write(json.dumps(headline) + "\n")


def _headline(matrix_rows):
    """A bench.py headline as emitted on a CONFIGS=subset run: the
    top-level value is the stale bert_base row; per-config truth lives
    in the nested matrix."""
    return {
        "metric": "bert_base_seq512_train_throughput",
        "value": 100.0, "unit": "samples/sec/chip", "mfu": 0.5,
        "platform": "tpu",
        "matrix": {"bert_base": {"value": 100.0,
                                 "unit": "samples/sec/chip",
                                 "mfu": 0.5},
                   **matrix_rows},
    }


@pytest.mark.smoke
class TestSummarizeOnchip:
    def _run(self, logdir):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin",
                                          "summarize_onchip.py"), logdir],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        return r.stdout

    def test_winner_uses_per_config_matrix_value(self, tmp_path):
        """Regression (ADVICE high): with the stale bert_base headline
        identical across variants, the winner must come from the
        per-config rows — here lc 1024x2048 despite 512x1024 sorting
        last... and first lexicographically."""
        d = str(tmp_path)
        _write_stage(d, "lc_512x1024", _headline(
            {"long_context": {"value": 5.0, "unit": "tok/s", "mfu": 0.2}}))
        _write_stage(d, "lc_1024x2048", _headline(
            {"long_context": {"value": 7.0, "unit": "tok/s", "mfu": 0.3}}))
        out = self._run(d)
        assert "long-context winner: blocks 1024,2048 (7.0)" in out
        # the per-stage table shows each variant's own number, not 100.0
        assert "lc_512x1024" in out and "5.0" in out

    def test_all_equal_group_prints_no_winner(self, tmp_path):
        """The old code max()ed identical values and crowned a winner by
        label string comparison; an all-equal group must print none."""
        d = str(tmp_path)
        for tok in ("1024", "2048", "4096"):
            _write_stage(d, f"moe_t{tok}", _headline(
                {"moe": {"value": 3.0, "unit": "tok/s", "mfu": 0.1}}))
        out = self._run(d)
        assert "moe winner" not in out
        assert "no winner to re-run" in out

    def test_bert4l_flash_ab_ranks_measurements(self, tmp_path):
        d = str(tmp_path)
        _write_stage(d, "bert4l_noflash", _headline(
            {"bert4l": {"value": 1987.0, "unit": "samples/sec/chip"}}))
        _write_stage(d, "bert4l_flash", _headline(
            {"bert4l": {"value": 630.0, "unit": "samples/sec/chip"}}))
        out = self._run(d)
        # noflash measured faster: flash=0 wins (old code: '1' > '0'
        # string tie-break always crowned flash)
        assert "bert4l winner: flash=0 (1987.0)" in out


class TestSuiteLock:
    def test_suite_flock_refusal_is_75(self, tmp_path):
        """bin/run_onchip_suite.sh itself exits 75 when the lock is
        held.  The holder script must NOT tail-exec the suite (bash
        would hand the locked fd over and the re-open would release
        it), so the suite runs mid-script with commands after it."""
        script = (
            "cd %s || exit 98\n"
            "exec 9>.onchip_suite.lock\n"
            "flock -n 9 || exit 99\n"
            "bash bin/run_onchip_suite.sh %s/log\n"
            "ec=$?\n"
            "exit $ec\n" % (REPO, tmp_path))
        r = subprocess.run(["bash", "-c", script], capture_output=True,
                           text=True, timeout=60)
        assert r.returncode == 75, (r.returncode, r.stdout, r.stderr)
        assert "refusing" in r.stderr


@pytest.mark.smoke
class TestBenchProvenance:
    def test_retry_recorder_keeps_better_and_records_discarded(self):
        import bench

        # retry wins: kept value updated, FIRST reading recorded
        probes, numeric = {48: 64.6}, {48: 64.6}
        bench._record_retry_probe(probes, numeric, 48, 64.6, 216.0)
        assert probes[48] == numeric[48] == 216.0
        assert probes["48_first_reading"] == 64.6
        assert "48_retry_reading" not in probes

        # retry loses: kept value unchanged, RETRY reading recorded —
        # never a duplicate of the kept value (the ADVICE regression)
        probes, numeric = {48: 216.0}, {48: 216.0}
        bench._record_retry_probe(probes, numeric, 48, 216.0, 60.0)
        assert probes[48] == numeric[48] == 216.0
        assert probes["48_retry_reading"] == 60.0
        assert "48_first_reading" not in probes

        # failed/skipped retry records nothing
        probes, numeric = {48: 216.0}, {48: 216.0}
        bench._record_retry_probe(probes, numeric, 48, 216.0,
                                  "RuntimeError: probe failed")
        assert set(probes) == {48}

    def test_bench_lm_records_flash_forced(self, monkeypatch):
        import bench

        monkeypatch.setattr(bench, "_build_lm",
                            lambda *a, **kw: None)
        monkeypatch.setattr(bench, "_time_steps",
                            lambda fn, iters, loss_fn: (0.1, 0.0))
        monkeypatch.delenv("HETU_BENCH_FORCE_FLASH", raising=False)
        out = bench._bench_lm("cpu", True, layers_n=2, seq=64,
                              per_chip_batch=2, iters=2)
        assert "flash_forced" not in out

        monkeypatch.setenv("HETU_BENCH_FORCE_FLASH", "1")
        out = bench._bench_lm("cpu", True, layers_n=2, seq=64,
                              per_chip_batch=2, iters=2)
        assert out["flash_forced"] is True and out["flash_attention"]

        monkeypatch.setenv("HETU_BENCH_FORCE_FLASH", "0")
        out = bench._bench_lm("cpu", True, layers_n=2, seq=64,
                              per_chip_batch=2, iters=2)
        assert out["flash_forced"] is True
        assert out["flash_attention"] is False
