"""The harness finds everything by name, and its traffic replays."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.chip import spec, traffic

ROOT = spec.ROOT
BENCH = spec.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.resolve(cell)
    assert c.config["name"] in {x["name"] for x in BENCH["configs"]}
    assert c.traffic["kind"] in ("sessions", "synthetic_lm")
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m.moves in names and callable(m.read)


def test_config_files_state_what_a_cell_needs():
    for cfg in BENCH["configs"]:
        c = spec.load_json(ROOT / cfg["file"])
        for key in ("source", "reduced", "assumed", "deployment", "model",
                    "check"):
            assert key in c, (cfg["name"], key)
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert c["source"] == cfg["source"]


def test_a_cell_defined_only_in_data_loads(tmp_path):
    bench = json.loads(json.dumps(BENCH))
    shutil.copytree(spec.HERE / "metrics", tmp_path / "metrics")
    (tmp_path / "traffic").mkdir()
    mix = spec.load_json(spec.HERE / "traffic" / "chat.json")
    mix["rate_per_s"] = 0.5
    (tmp_path / "traffic" / "slow.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": "qwen3-1.7b.slow",
                               "config": "qwen3-1.7b", "traffic": "slow",
                               "chips": 1, "why": "a test cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen3-1.7b.chat" in m.get("workloads", []):
            m["workloads"].append("qwen3-1.7b.slow")
    c = spec.resolve("qwen3-1.7b.slow", bench=bench, bench_dir=tmp_path)
    assert c.traffic["rate_per_s"] == 0.5
    assert {m.name for m in c.per_layer} >= {"prefix_hit_token_share"}


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(spec.HERE / "run.py"),
                        "--workload", "qwen3-1.7b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def _key(reqs):
    return [(r.rid, r.due_s, r.max_new_tokens, r.prefix_id,
             r.tokens.tobytes()) for r in reqs]


SESSION_MIXES = sorted(p.stem for p in (spec.HERE / "traffic").glob("*.json")
                       if spec.load_json(p)["kind"] == "sessions")


@pytest.mark.parametrize("mix", SESSION_MIXES)
def test_sessions_replay_and_share_the_work(mix):
    m = spec.load_json(spec.HERE / "traffic" / f"{mix}.json")
    seed = 2 ** 31 + 977
    a = traffic.sessions(m, seed, 30.0, 151936)
    b = traffic.sessions(m, seed, 30.0, 151936)
    assert _key(a) == _key(b)                    # byte-identical replay
    c = traffic.sessions(m, seed + 1, 30.0, 151936)
    assert _key(a) != _key(c)
    # another seed: the same work (gaps, lengths, system prompts) in
    # another order, with other tokens
    gaps = lambda rs: np.diff([0.0] + [r.due_s for r in rs])
    work = lambda rs: (sorted(np.round(gaps(rs), 9)),
                       sorted(r.tokens.size for r in rs),
                       sorted(r.max_new_tokens for r in rs),
                       sorted(r.prefix_id for r in rs))
    assert work(a) == work(c)
    order = lambda rs: [(r.tokens.size, r.max_new_tokens) for r in rs]
    assert order(a) != order(c)
    assert not any(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))
    assert all(r.due_s < 30.0 for r in a)
    lo, hi = traffic.prompt_length_range(m)
    assert max(r.tokens.size for r in a) <= hi
    shared = [r for r in a if r.prefix_id >= 0]
    assert abs(len(shared) / len(a) - m.get("shared_share", 0)) < 0.05
    for r in shared:
        assert np.array_equal(r.tokens[: m["system_len"]],
                              shared[[s.prefix_id for s in shared].index(
                                  r.prefix_id)].tokens[: m["system_len"]])


@pytest.mark.parametrize("mix", SESSION_MIXES)
def test_warmup_covers_every_bucket(mix):
    m = spec.load_json(spec.HERE / "traffic" / f"{mix}.json")
    lo, hi = traffic.prompt_length_range(m)
    lens = traffic.warmup_lengths(m)
    bucket = lambda n: 1 << max(3, (n - 1).bit_length())
    assert {bucket(n) for n in range(lo, hi + 1)} <= {bucket(n) for n in lens}


def test_training_batches_replay():
    a = traffic.synthetic_lm(1000, 2, 128, 2 ** 31 + 5, 3)
    b = traffic.synthetic_lm(1000, 2, 128, 2 ** 31 + 5, 3)
    c = traffic.synthetic_lm(1000, 2, 128, 2 ** 31 + 5, 4)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert len({r.tobytes() for r in a["tokens"]}) == 2


def test_benchmark_file_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()
    for m in BENCH["per_layer"]:
        assert (spec.HERE / "metrics" / f"{m['name']}.py").is_file()
