"""The system under test, as a deployment runs it: one process holds the
chip(s), a `CoordinatorServer` on a loopback port serves the configuration's
runner, and `trino_tpu.client.Client` drives it over HTTP.  This module is
the only one that imports the program; it also reads the program's own
spans and counters for the traced run."""

from __future__ import annotations


class Served:
    def __init__(self, config: dict, trace: bool):
        import trino_tpu  # noqa: F401  (enables x64, the engine needs i64)
        from trino_tpu.client import Client
        from trino_tpu.server.coordinator import CoordinatorServer

        self.config = config
        self.runner = self._runner(config)
        for name, value in (config.get("session") or {}).items():
            self.runner.properties.set(name, value)
        if trace:
            self.runner.properties.set("query_trace", True)
        self.server = CoordinatorServer(runner=self.runner, port=0)
        self.server.start()
        self.url = f"http://127.0.0.1:{self.server.port}"
        self._client = Client

    @staticmethod
    def _runner(config: dict):
        how = config["runner"]
        if how["kind"] == "local":
            from trino_tpu.runtime.runner import LocalQueryRunner

            return LocalQueryRunner(
                catalog=config["catalog"], schema=config["schema"],
                target_splits=int(how["target_splits"]),
            )
        if how["kind"] == "distributed":
            from trino_tpu.parallel import DistributedQueryRunner

            return DistributedQueryRunner(
                catalog=config["catalog"], schema=config["schema"],
                n_workers=int(how["n_workers"]),
            )
        raise ValueError(f"unknown runner kind {how['kind']!r}")

    def client(self):
        """A client of its own for each stream (no shared state)."""
        return self._client(self.url)

    # -- what the traced run reads from the program ---------------------------

    def drain_spans(self) -> list:
        """The flattened span trees of the statements finished since the
        last call: [(query_id, [span dict, ...]), ...]."""
        out = []
        traces = self.runner.traces
        while True:
            try:
                out.append(traces.popleft())
            except IndexError:
                return out

    def counters(self) -> dict:
        """The scan buffer pool's counters, and the size of each entry of its
        device tier (for the estimate of bytes moved host->device)."""
        from trino_tpu.runtime.buffer_pool import POOL

        out = dict(POOL.stats())
        with POOL.lock:
            out["device_entries"] = {
                repr(k): int(v[1]) for k, v in POOL.device.entries.items()
            }
        return out

    def mesh_profile(self):
        """The last distributed statement's profile as JSON, or None."""
        profile = getattr(self.runner, "last_mesh_profile", None)
        return None if profile is None else profile.to_json()

    def clear_pool(self) -> None:
        from trino_tpu.runtime.buffer_pool import POOL

        POOL.clear()

    def close(self) -> None:
        """Stop the server and free what the program holds on the device, so
        that the reference runs beside nothing."""
        import jax

        self.server.shutdown()
        self.clear_pool()
        self.runner = None
        self.server = None
        jax.clear_caches()
