"""The seeded byte model: what every object must hold.

All bytes the benchmark writes come from ``--seed`` and are made before
the window opens.  Object *names* never depend on the seed (placement
is the same in every run); contents, offsets and order do.
"""
import numpy as np


class ByteModel:
    def __init__(self, seed: int, traffic: dict):
        self.object_bytes = traffic["object_bytes"]
        self.names = traffic["names"]
        self.n_populated = traffic.get("populate_objects", 0)
        n_pool = max(traffic.get("payload_pool", 1), 1)
        rng = np.random.default_rng([abs(int(seed)), 0xEC0B1EC7])
        self.pool = [rng.bytes(self.object_bytes) for _ in range(n_pool)]
        # object number -> pool slot: a seeded affine walk, so that
        # neighbours differ and a swapped object shows
        self._a = int(rng.integers(0, n_pool // 2 or 1)) * 2 + 1
        self._b = int(rng.integers(0, n_pool))
        self.patch_bytes = 0
        self.patches = []
        for op in traffic["ops"]:
            if op["op"] == "write":
                self.patch_bytes = op["io_bytes"]
        if self.patch_bytes:
            blob = rng.bytes(self.patch_bytes * traffic["patch_pool"])
            self.patches = [blob[i * self.patch_bytes:
                                 (i + 1) * self.patch_bytes]
                            for i in range(traffic["patch_pool"])]
        self.rng = rng                  # the generator's plan draws on
        self._overlay = {}              # object number -> bytearray

    def name(self, n: int) -> str:
        return self.names.format(n=n)

    def base(self, n: int) -> bytes:
        """What write_full puts into object ``n``."""
        return self.pool[(n * self._a + self._b) % len(self.pool)]

    def patch(self, n: int, offset: int, patch_index: int) -> None:
        buf = self._overlay.get(n)
        if buf is None:
            buf = self._overlay[n] = bytearray(self.base(n))
        p = self.patches[patch_index]
        buf[offset:offset + len(p)] = p

    def current(self, n: int) -> bytes:
        buf = self._overlay.get(n)
        return self.base(n) if buf is None else bytes(buf)
