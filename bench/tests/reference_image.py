"""Prints checksums of reference images of the test-only tiny
configuration, as JSON: ``{"<sh degree>": "<sha256 of the float32
image>"}`` for SH degrees 0 and 1, scene seed 7, orbit angle 0.4 at 3.0
extents. Run with ``XLA_FLAGS=--xla_cpu_max_isa=AVX2`` so that the CPU's
matrix products round alike on every x86 host:

    XLA_FLAGS=--xla_cpu_max_isa=AVX2 JAX_PLATFORMS=cpu \\
        python3 bench/tests/reference_image.py
"""
import hashlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import reference  # noqa: E402
from bench.poses import orbit_pose  # noqa: E402
from bench.scene import make_scene  # noqa: E402


def main():
    conf = json.loads((ROOT / "bench/tests/data/tiny.json").read_text())
    orbit = dict(conf["orbit"], radius=3.0 * conf["extent"])
    pose = orbit_pose(0.4, orbit, conf["width"], conf["height"])
    out = {}
    for degree in (0, 1):
        scene = make_scene(7, dict(conf, sh_degree=degree))
        img, _ = reference.render(scene, pose,
                                  capacity=conf["correct"]["pair_capacity"])
        img = np.ascontiguousarray(np.asarray(img, np.float32))
        out[str(degree)] = hashlib.sha256(img.tobytes()).hexdigest()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
