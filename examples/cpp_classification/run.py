#!/usr/bin/env python
"""Build + exercise the C++ classification example end to end
(reference examples/cpp_classification/readme.md workflow): compile
classification.cc against the embedded CPython, generate a toy
deploy/weights/labels/image, run the binary, and assert it prints five
"score - "label"" lines with descending scores summing to ~1.

Usage: python examples/cpp_classification/run.py
"""

import os
import subprocess
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.abspath(os.path.join(_HERE, "..", ".."))
sys.path.insert(0, _ROOT)

import numpy as np  # noqa: E402


def build(binary: str) -> None:
    cfg = lambda *a: subprocess.run(
        ["python3-config", *a], capture_output=True, text=True,
        check=True).stdout.split()
    cmd = ["g++", "-O2", os.path.join(_HERE, "classification.cc"),
           "-o", binary, *cfg("--includes"), *cfg("--ldflags", "--embed")]
    subprocess.run(cmd, check=True)


def main(argv=None) -> int:
    import caffe_mpi_tpu.pycaffe as caffe
    from PIL import Image

    with tempfile.TemporaryDirectory() as tmp:
        model = os.path.join(tmp, "deploy.prototxt")
        with open(model, "w") as f:
            f.write("""
name: "toy"
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: 1 dim: 3 dim: 8 dim: 8 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "score"
        inner_product_param { num_output: 5
          weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "score" top: "prob" }
""")
        weights = os.path.join(tmp, "w.caffemodel")
        caffe.Net(model, caffe.TEST).save(weights)
        labels = os.path.join(tmp, "labels.txt")
        with open(labels, "w") as f:
            f.write("\n".join(f"class_{i}" for i in range(5)))
        img = os.path.join(tmp, "cat.png")
        Image.fromarray(np.random.RandomState(0).randint(
            0, 255, (12, 12, 3), np.uint8)).save(img)

        binary = os.path.join(tmp, "classification")
        build(binary)
        env = dict(os.environ,
                   PYTHONPATH=_ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   # the toy classify runs on the host CPU: the embedded
                   # interpreter must not claim the chip for a 5-class
                   # demo net
                   JAX_PLATFORMS="cpu")
        r = subprocess.run([binary, model, weights, labels, img],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        print(r.stdout, end="")
        assert r.returncode == 0, r.stderr[-2000:]
        lines = [l for l in r.stdout.splitlines() if " - " in l]
        assert len(lines) == 5, lines
        scores = [float(l.split(" - ")[0]) for l in lines]
        assert scores == sorted(scores, reverse=True)
        assert abs(sum(scores) - 1.0) < 1e-3
        assert all('"class_' in l for l in lines)
    print("cpp_classification example OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
