"""A configuration, a traffic mix, a cell and a per-layer metric are added
as NEW files and NEW entries; no file that is there is edited."""
import filecmp
import json
import os

from benchmark.harness import cells, runner

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _same_tree(a, b):
    """Every file of tree ``a`` is in ``b`` unchanged."""
    for dirpath, dirs, files in os.walk(a):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if f.endswith(".pyc"):
                continue
            src = os.path.join(dirpath, f)
            dst = os.path.join(b, os.path.relpath(src, a))
            assert filecmp.cmp(src, dst, shallow=False), src


def test_new_files_are_picked_up_with_no_edit(tiny_root):
    bench_dir = os.path.join(tiny_root, "benchmark")
    # helpers.make_root ADDED tiny configurations, their references and
    # mixes; every file the benchmark had is still there, byte for byte
    _same_tree(BENCH, bench_dir)
    # a new per-layer metric: one reader file and one entry
    with open(os.path.join(bench_dir, "metrics", "steps_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['steps']\n")
    with open(os.path.join(bench_dir, "metrics", "nothing_to_read.py"),
              "w") as f:
        f.write("def read(ctx):\n    return None\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for name in ("steps_seen", "nothing_to_read"):
        bench["per_layer"].append(
            {"name": name, "unit": "steps", "better": "higher",
             "source": "program_counter", "layer": "Decode",
             "moves": "decode_tok_per_s", "workloads": ["tiny-lm.closed"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    cell = cells.Cell(tiny_root, "tiny-lm.closed")
    assert cell.config["hidden_size"] == 64 and cell.traffic["clients"] == 5
    assert hasattr(cell.reference(), "forward")
    assert [m["name"] for m in cell.per_layer] == [
        "compile_s", "steps_seen", "nothing_to_read"]
    other = cells.Cell(tiny_root, "tiny-lm.open")
    assert [m["name"] for m in other.per_layer] == ["compile_s"]

    class FakeRun(object):
        pass

    run = FakeRun()
    run.cell = cell
    values = runner.per_layer_metrics(run, {"steps": 7,
                                            "setup_compile_s": 1.5})
    # a reader that finds nothing to read is left out of the line
    assert values == {"compile_s": 1.5, "steps_seen": 7.0}
