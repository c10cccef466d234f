"""The deployment under test: one ``ValidatorNode`` with the HTTP endpoint and
one ``WorkerNode``, in this process (it owns the chips; the node runners'
network processes stay JAX-free). The pattern is ``chip_smoke.py``'s,
copied here so that a later change to that script cannot move the
yardstick.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import time


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


def http_json(port: int, method: str, path: str, body=None, timeout=600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        hdrs = {"Content-Type": "application/json"} if payload else {}
        conn.request(method, path, body=payload, headers=hdrs)
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    return resp.status, (json.loads(data) if data else {})


def ml_config(deployment: dict):
    """The repo's default ``MLConfig`` with the deployment's overrides."""
    from tensorlink_tpu.core.config import MLConfig

    over = dict(deployment.get("ml", {}))
    for k, v in over.items():
        if isinstance(v, list):
            over[k] = tuple(v)
    return dataclasses.replace(MLConfig(), **over)


def model_config_json(config: dict) -> dict:
    """The configuration file's published keys as the inline config that
    ``POST /request-model`` takes, through the program's own reader of a
    checkpoint's ``config.json``."""
    from tensorlink_tpu.models.registry import config_from_hf

    return config_from_hf(dict(config)).to_json()


def install_tokenizer(vocab_size: int) -> None:
    """Every model hosted from now on gets the benchmark's full-vocabulary
    tokenizer (see ``tokenizer.py``) where the program would fall back to
    bytes."""
    from tensorlink_tpu.api import tokenizer as tok_mod

    from .tokenizer import FullVocabTokenizer

    def load_tokenizer(model_spec: dict):
        return tok_mod.TokenizerAdapter(FullVocabTokenizer(vocab_size))

    tok_mod.load_tokenizer = load_tokenizer


class Cluster:
    def __init__(self, ml, tmp: str):
        from tensorlink_tpu.core.config import ValidatorConfig, WorkerConfig
        from tensorlink_tpu.nodes.runners import ValidatorNode, WorkerNode

        common = dict(local_test=True, log_dir=f"{tmp}/logs",
                      env_file=f"{tmp}/.env")
        self.validator = ValidatorNode(ValidatorConfig(
            endpoint=True, endpoint_port=0, key_dir=f"{tmp}/keys_v", ml=ml,
            **common,
        )).start()
        self.worker = None
        try:
            self.worker = WorkerNode(WorkerConfig(
                seed_validators=[["127.0.0.1", self.validator.port]],
                key_dir=f"{tmp}/keys_w", ml=ml, **common,
            )).start()
            deadline = time.monotonic() + 30
            while not self.validator.status()["peers"]:
                if time.monotonic() > deadline:
                    raise BenchFailure("worker never connected to the validator")
                time.sleep(0.05)
        except BaseException:
            self.stop()
            raise
        self.port = self.validator.api.port

    def stop(self) -> None:
        if self.worker is not None:
            self.worker.stop()
            self.worker = None
        if self.validator is not None:
            self.validator.stop()
            self.validator = None

    def host(self, name: str, config_json: dict, seq_len: int) -> None:
        status, out = http_json(self.port, "POST", "/request-model", {
            "hf_name": name, "seq_len": int(seq_len), "config": config_json,
        })
        if status != 200 or out.get("status") != "ready":
            raise BenchFailure(f"/request-model {name}: {status} {out}")

    def engine(self):
        """The hosted job's slot engine on the worker (built lazily by the
        first continuous request)."""
        jobs = list(self.worker.executor.jobs.values())
        if len(jobs) != 1 or jobs[0].cont is None:
            raise BenchFailure(
                f"worker holds {len(jobs)} job(s) and "
                f"{'no slot engine' if jobs else 'nothing'}: the request was "
                "not served by the ContinuousEngine"
            )
        return jobs[0].cont
