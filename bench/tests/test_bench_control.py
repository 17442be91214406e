"""The control: the reference computed at fp8's precision, put in the
program's place, fails the limits of ``correct`` that the program
itself meets, read on the same prompts and served tokens (at a small
size, on a fixed set of the cell's requests served to the end; the
readings at the cells' own sizes are in PERF.md)."""
import pytest

from bench import calibrate, correctness
from bench import model as bmodel
from bench.tests.tiny import tiny_cell
from bench.traffic.generator import Generator


@pytest.mark.parametrize("name", ["internlm2-chat", "dsmoe16b-batch"])
def test_control_fails_where_the_program_passes(name):
    from repro.serving.request import Request

    cell, seed = tiny_cell(name), 3
    conf = cell.config
    model, _, engine = bmodel.build_engine(conf, seed)
    gen = Generator(cell.mix, seed, conf["vocab_size"])
    reqs = []
    for _ in range(8):
        spec = gen.next()
        reqs.append(Request(prompt_tokens=spec.prompt,
                            max_new_tokens=spec.max_new, ignore_eos=True))
        engine.submit(reqs[-1])
    engine.run_until_done()
    engine.close()
    done = [correctness.Served(list(r.prompt_tokens), list(r.output_tokens))
            for r in reqs]
    r = calibrate.judge(conf, model, seed, done, control=True,
                        own_process=False)
    limits = conf["limits"]
    assert r["tokens"] >= 200, r
    assert all(r["program"][k] <= v for k, v in limits.items()), r
    assert any(r["reference_fp8"][k] > v for k, v in limits.items()), r
