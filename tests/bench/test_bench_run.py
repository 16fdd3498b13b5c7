"""``chipbench/run.py`` refuses to run anywhere but on a TPU: exit code 2
and no result line."""
from chipbench import run


def test_refuses_to_run_off_a_tpu(tmp_path, capsys, monkeypatch):
    # keep main's process-wide settings out of this process
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path / "tpu_logs"))
    assert run.main(["--workload", "qwen2_7b.decode", "--seed", str(2**33 + 1),
                     "--seconds", "1", "--trace", "0"]) == 2
    out, err = capsys.readouterr()
    assert "JAX found no TPU" in err
    assert not any(line.startswith("{") for line in out.splitlines())


def test_sample_covers_every_chunk_size_and_the_longest():
    import numpy as np

    from chipbench.engines.closed_batch import Chunk, Job

    def job(i, P, ks):
        j = Job(i, np.zeros((4, P), np.int32), 0.0, finished=True)
        j.chunks = [Chunk(k, 1.0, 2.0, P, False) for k in ks]
        return j

    jobs = [job(0, 8, [1, 2]), job(1, 8, [4, 4]), job(2, 32, [8]), job(3, 8, [2, 2]), job(4, 8, [1])]
    jobs[4].finished = False
    picked = run.choose_sample(jobs, (0.0, 10.0), 2**33 + 5, 6)
    idx = [j.index for j, _ in picked]
    assert {0, 1, 2} <= set(idx) and 4 not in idx
    assert len(picked) == 6 and len(set((j.index, r) for j, r in picked)) == 6
    assert picked == run.choose_sample(jobs, (0.0, 10.0), 2**33 + 5, 6)
