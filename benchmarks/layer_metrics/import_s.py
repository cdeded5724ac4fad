"""Seconds the package's import took, from the first line of
``tpu_resnet/__init__.py`` to the end of ``tpu_resnet/train/loop.py``'s
module body (orbax, the model families, the kernels, and JAX where the
caller had not imported it): the loop's ``import_sec``, the length of its
``process.import`` span, which lies inside ``process.before_train``."""


def read(run):
    if not run.records or "import_sec" not in run.records[0]:
        return None
    return float(run.records[0]["import_sec"])
