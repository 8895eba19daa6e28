"""The client side of a run: requests into the program's ``RenderServer``,
frames back out, each stamped on the host clock.

Every request goes in through ``RenderServer.submit`` and is dispatched by
``step``/``drain``, which hand the batch to the committed engine handle's
``render_batch``. The server copies each image to the host before ``step``
returns, so a frame is done, and stamped, when ``step`` or ``drain`` hands
it back. The benchmark's own ``TraceAnnotation`` spans (``bench/submit``,
``bench/step``, ``bench/drain``) mark what the host was doing, for the
traced run's idle gaps.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench.poses import Pose, orbit_pose


@dataclasses.dataclass
class Frame:
    rid: int
    pose: Pose
    submit_t: float
    done_t: Optional[float] = None
    image: Optional[np.ndarray] = None
    warm: bool = False


class Session:
    """Submits orbit poses to ``server`` and collects the frames.

    ``keep(frame)`` decides, as each frame completes, whether its image is
    kept for the correctness check; the others are dropped at once, so a run
    that completes many frames holds only its sample.
    """

    def __init__(self, server, scene_id, cfg, orbit, width, height,
                 make_camera, make_request, annotate, keep=lambda f: True):
        self.server = server
        self.scene_id = scene_id
        self.cfg = cfg
        self.orbit = orbit
        self.width, self.height = width, height
        self._camera = make_camera
        self._request = make_request
        self._annotate = annotate
        self._keep = keep
        self.frames: List[Frame] = []
        self._open: Dict[int, Frame] = {}
        self.warming = True

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def in_flight(self) -> int:
        return len(self._open)

    def submit(self, angle: float) -> bool:
        pose = orbit_pose(angle, self.orbit, self.width, self.height)
        rid = len(self.frames)
        req = self._request(rid, self.scene_id, self._camera(pose), self.cfg)
        with self._annotate("bench/submit"):
            t = self.now()
            ok = self.server.submit(req)
        frame = Frame(rid, pose, t, warm=self.warming)
        self.frames.append(frame)
        if ok:
            self._open[rid] = frame
        return ok

    def _collect(self) -> None:
        t = self.now()
        results = self.server.results
        for rid in [r for r in self._open if r in results]:
            frame = self._open.pop(rid)
            frame.done_t = t
            image = results.pop(rid).image
            if frame.warm or self._keep(frame):
                frame.image = image

    def step(self) -> None:
        with self._annotate("bench/step"):
            self.server.step()
        self._collect()

    def drain(self) -> None:
        with self._annotate("bench/drain"):
            self.server.drain()
        self._collect()

    def window(self) -> List[Frame]:
        return [f for f in self.frames if not f.warm]
