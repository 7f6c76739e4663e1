"""The four-chip cell rehearsed on four virtual CPU devices: the sidecar takes
the catalog mesh by itself and the bit-identity check runs."""

from bench_rehearsal import assert_rehearsal, last_line, run_cell


def test_mesh4_rehearsal_on_four_virtual_devices():
    proc = run_cell("backlog-50k-mesh4.full", "--trace", "1", "--rehearse", devices=4)
    result = last_line(proc)
    assert_rehearsal(result, {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "encode_s", "dispatch_s", "compiles_in_window", "first_request_s",
        "backend_compiles", "device_wait_s", "kernel_device_s", "decode_s", "fetch_s",
        "collective_share",
    }, traced=True, devices=4)
    assert '"failures"' not in proc.stdout  # mesh taken, sharded, bit-identical
    ops = " ".join(name for name, _ in result["breakdown"]["device_ops"])
    assert "all-reduce" in ops or "psum" in ops or "pmax" in ops


def test_mesh4_on_one_device_is_refused():
    proc = run_cell("backlog-50k-mesh4.full", "--trace", "0", "--rehearse")
    assert proc.returncode == 2 and proc.stdout == ""
