"""The benchmark refuses to run, and prints no result, without a TPU."""
import json
from types import SimpleNamespace

from bench import run


def test_no_tpu_exits_2_with_no_result(capsys):
    rc = run.main(["--workload", "r3_placed", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert "platform=cpu" in out.out
    assert "no TPU" in out.err
    for line in out.out.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_too_few_chips_is_an_error():
    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert run.device_error([tpu], 1) is None
    assert "needs 4 chip(s)" in run.device_error([tpu], 4)


def test_unknown_device_kind_has_no_peaks():
    import pytest

    from bench.peaks import peaks
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v99")
