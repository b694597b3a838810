"""The serving launcher (``python -m repro.launch.serve``) end to end on the
qwen2-1.5b smoke preset: each engine it offers serves every request and
prints one ``req N: [...]`` line per request."""
import pytest

from repro.launch import serve

N_REQUESTS = 3


@pytest.mark.parametrize("extra", [
    [],
    ["--temperature", "0.8", "--top-k", "5", "--seed", "1"],
    ["--engine", "reference"],
], ids=["ragged-greedy", "ragged-sampled", "reference"])
def test_launcher_serves_every_request(extra, capsys, monkeypatch, tmp_path):
    # a set cache directory is returned as is: the launcher then leaves the
    # process's JAX config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    rc = serve.main(["--arch", "qwen2-1.5b", "--smoke",
                     "--requests", str(N_REQUESTS), "--batch-size", "2",
                     "--max-tokens", "3"] + extra)
    assert rc == 0
    out = capsys.readouterr().out.splitlines()
    reqs = [line for line in out if line.startswith("req ")]
    assert len(reqs) == N_REQUESTS, out
    for line in reqs:
        tokens = line.split(":", 1)[1].strip()
        assert tokens.startswith("[") and tokens.count(",") == 2, line
