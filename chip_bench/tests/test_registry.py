"""Cells, traffic mixes and metrics are found by name: a new mix and a new
metric, each a file of its own, are picked up with no existing file edited."""
import json
import shutil

import numpy as np

from chip_bench import harness, spec, traffic


def test_new_traffic_and_metric_files_are_picked_up(tmp_path):
    root = tmp_path / "checkout"
    bench_dir = root / "chip_bench"
    shutil.copytree(spec.BENCH_DIR / "traffic", bench_dir / "traffic")
    shutil.copytree(spec.BENCH_DIR / "configs", bench_dir / "configs")
    bench = spec.load_benchmark()
    before = {p: p.read_bytes() for p in (bench_dir / "traffic").iterdir()}

    mix = json.loads((bench_dir / "traffic" / "flan-mix.json").read_text())
    mix.update(why="short prompts only", max_len=128,
               enc=dict(mix["enc"], mean_range=[8, 64], clip=[4, 128]))
    (bench_dir / "traffic" / "short-128.json").write_text(json.dumps(mix))
    (bench_dir / "metrics").mkdir()
    (bench_dir / "metrics" / "mean_batch_samples.py").write_text(
        "def read(w):\n"
        "    return sum(len(it['lengths']) for it in w.iterations)"
        " / len(w.iterations)\n")
    bench["workloads"].append({"name": "gpt-paper-2L.short-128",
                               "config": "gpt-paper-2L",
                               "traffic": "short-128", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "mean_batch_samples", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "planner", "moves": "real_tokens_per_s"})
    bench["configs"] = [dict(c, file=c["file"]) for c in bench["configs"]]

    cell = spec.find_cell("gpt-paper-2L.short-128", root=root,
                          bench_dir=bench_dir, bench=bench)
    assert cell.traffic["max_len"] == 128
    assert "mean_batch_samples" in [m["name"] for m in cell.per_layer]
    old = spec.find_cell("gpt-paper-2L.flan-mix", root=root,
                         bench_dir=bench_dir, bench=bench)
    assert "mean_batch_samples" in [m["name"] for m in old.per_layer]

    gbs = traffic.build(cell.traffic, 50304, False, 5, 6, 4)
    assert max(int(g.lengths.max()) for g in gbs) <= 128
    w = harness.Window(model=cell.config["model"], peak={}, chips=1,
                       seconds=1.0, compiles=0,
                       iterations=[{"lengths": g.lengths} for g in gbs])
    read = spec.metric_reader("mean_batch_samples", bench_dir=bench_dir)
    assert read(w) == np.mean([g.n_samples for g in gbs])
    for p, data in before.items():
        assert p.read_bytes() == data
