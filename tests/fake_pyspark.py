"""Process-backed stand-in for the pyspark surface ``horovod_tpu.spark.run``
touches.

Real Spark local mode cannot run here (no network egress to install
pyspark, no JVM — see tests/test_spark.py's module docstring), so this
module implements the exact API slice ``spark/__init__.py::run`` drives —
``SparkContext._active_spark_context``, ``defaultParallelism``,
``parallelize(...).mapPartitionsWithIndex(f).collect()`` — with the same
EXECUTION SEMANTICS local Spark gives it:

  * each partition runs in its own PYTHON PROCESS (Spark's python workers
    are separate processes; per-process env vars is exactly what
    ``_task_fn``'s ``os.environ.update`` relies on),
  * the partition function travels by CLOUDPICKLE (what real pyspark uses
    for closures), so the closure over (fn, args, driver_addr) is
    serialized/deserialized the same way,
  * ``collect`` returns the concatenated per-partition results in
    partition order (reference result channel, spark/__init__.py:223-227).

Used by tests/test_spark_e2e.py by installing this module as
``sys.modules["pyspark"]`` before importing ``horovod_tpu.spark``.
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class _RDD:
    def __init__(self, items, num_slices):
        self._items = list(items)
        self._num_slices = num_slices

    def mapPartitionsWithIndex(self, f):  # noqa: N802 — pyspark casing
        rdd = _RDD(self._items, self._num_slices)
        rdd._fn = f
        return rdd

    def _partitions(self):
        n = self._num_slices
        per = len(self._items) // n
        extra = len(self._items) % n
        out, i = [], 0
        for p in range(n):
            take = per + (1 if p < extra else 0)
            out.append(self._items[i:i + take])
            i += take
        return out

    def collect(self):
        import cloudpickle

        procs = []
        for idx, part in enumerate(self._partitions()):
            payload = tempfile.NamedTemporaryFile(
                suffix=f".part{idx}.pkl", delete=False)
            payload.write(cloudpickle.dumps((self._fn, idx, part)))
            payload.close()
            result_path = payload.name + ".out"
            env = dict(os.environ)
            env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
            # Executors must not contend for the TPU the test parent holds.
            env["JAX_PLATFORMS"] = "cpu"
            env.setdefault("HOROVOD_CYCLE_TIME", "1")
            procs.append((idx, payload.name, result_path, subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), payload.name,
                 result_path],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)))

        results = []
        errors = []
        for idx, payload_path, result_path, proc in procs:
            try:
                out, _ = proc.communicate(timeout=180)
                if proc.returncode != 0:
                    errors.append(
                        f"partition {idx}: exit {proc.returncode}:\n{out}")
                else:
                    with open(result_path, "rb") as f:
                        results.extend(pickle.load(f))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()  # reap; kill() alone leaves a zombie
                errors.append(f"partition {idx}: timeout")
            finally:
                for p in (payload_path, result_path):
                    try:
                        os.unlink(p)
                    except OSError:
                        pass
        if errors:
            raise RuntimeError("executor failure:\n" + "\n".join(errors))
        return results


class SparkContext:
    _active_spark_context = None

    def __init__(self, master: str = "local[2]"):
        # local[N] — the only master the stand-in understands.
        self.defaultParallelism = int(master[len("local["):-1])
        SparkContext._active_spark_context = self

    def parallelize(self, items, numSlices=None):  # noqa: N803
        return _RDD(items, numSlices or self.defaultParallelism)

    def stop(self):
        SparkContext._active_spark_context = None


def _executor_main(payload_path: str, result_path: str) -> None:
    """Partition worker: evaluate the cloudpickled partition function the
    way a Spark python worker does, write the materialized results back."""
    import cloudpickle

    with open(payload_path, "rb") as f:
        fn, index, items = cloudpickle.loads(f.read())
    results = list(fn(index, iter(items)))
    with open(result_path, "wb") as f:
        pickle.dump(results, f)


if __name__ == "__main__":
    _executor_main(sys.argv[1], sys.argv[2])
