"""``tenant_churn`` rehearsed end to end in a fresh process on the CPU,
traced: the tenant path's spans feed the per-layer readers."""

from bench_rehearsal import assert_rehearsal, last_line, run_cell

# a rehearsal's cycle takes milliseconds, which the tenant plane's default
# admission (10 requests/s sustained) would shed; at the real size a cycle
# takes seconds.  Deployment settings of the program, not of the benchmark.
FAST = {"KC_TENANT_RATE": "100000", "KC_TENANT_BURST": "100000"}


def test_tenant_churn_rehearsal_traced():
    proc = run_cell("backlog-50k.churn", "--trace", "1", "--rehearse", **FAST)
    result = last_line(proc)
    assert_rehearsal(result, {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "session_self_s", "encode_s", "dispatch_s", "compiles_in_window",
        "first_request_s", "backend_compiles", "device_wait_s", "kernel_device_s",
        "decode_s", "fetch_s",
    }, traced=True)
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    # whole groups of 17 cycles, two calls a cycle
    assert result["attempted"] % 34 == 0


def test_tenant_churn_rehearsal_untraced_counts_the_pods_that_moved():
    result = last_line(run_cell("backlog-50k.churn", "--trace", "0", "--rehearse", **FAST))
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "setup_s"}, traced=False)
