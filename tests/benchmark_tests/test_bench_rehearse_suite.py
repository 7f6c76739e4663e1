"""``size_cycle`` rehearsed end to end in a fresh process on the CPU."""

import json

from bench_rehearsal import assert_rehearsal, last_line, run_cell


def test_size_cycle_rehearsal_traced():
    proc = run_cell("suite-400.mixed", "--trace", "1", "--rehearse")
    result = last_line(proc)
    assert_rehearsal(result, {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "encode_s", "dispatch_s", "compiles_in_window", "first_request_s",
        "backend_compiles", "device_wait_s", "kernel_device_s", "decode_s", "fetch_s",
    }, traced=True)
    gaps = dict(result["breakdown"]["idle_gaps"])
    assert "service.handler" in gaps
    assert result["attempted"] % 5 == 0  # whole cycles of the rehearsal's five sizes


def test_size_cycle_rehearsal_untraced_reports_no_tail_from_too_few_requests():
    proc = run_cell("suite-400.mixed", "--trace", "0", "--rehearse", seconds="0.2")
    result = last_line(proc)
    window = next(json.loads(line)["window"] for line in proc.stdout.splitlines()
                  if line.startswith('{"window"'))
    assert window["units"] < 100  # too few for ten beyond p90, let alone p95
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "setup_s"}, traced=False)
