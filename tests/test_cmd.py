"""Deploy entrypoints (karpenter_core_tpu/cmd) and the local bring-up."""

import subprocess


class TestEntrypoints:
    def test_load_cloud_provider(self):
        from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
        from karpenter_core_tpu.cmd.operator import load_cloud_provider

        provider = load_cloud_provider(
            "karpenter_core_tpu.cloudprovider.fake:FakeCloudProvider"
        )
        assert isinstance(provider, FakeCloudProvider)

    def test_run_local_check(self):
        """deploy/run_local.sh --check brings up the deployed topology (one
        shared solver + leader-elected operator replicas) from scratch and
        probes everything — the deploy artifact's contract."""
        import os

        env = dict(os.environ)
        env.update(
            BASE_METRICS_PORT="18280",
            KC_SOLVER_LISTEN="127.0.0.1:18980", JAX_PLATFORMS="cpu",
            KC_TPU_KERNEL="0", KC_TPU_WARMUP="0",
        )
        proc = subprocess.run(
            ["deploy/run_local.sh", "--check"],
            capture_output=True, text=True, timeout=180, env=env,
            cwd=subprocess.os.path.dirname(subprocess.os.path.dirname(__file__)) or ".",
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "topology is up" in proc.stdout
        assert "one leader elected" in proc.stdout
