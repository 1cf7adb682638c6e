"""The simulated statistics a configuration implies, from its layer
shapes alone: Domino's mapping and schedule rules (paper §5-6),
written down independently of the program.

* Mapping: a convolution's filter row is one tile group.  Where
  ``C <= n_c`` up to ``n_c // C`` taps of the row share a tile (in-buffer
  shifting), otherwise each tap's channels split over ``ceil(C / n_c)``
  tiles; ``ceil(M / n_m)`` such chains make one weight copy.  An FC
  layer is a ``ceil(C_in / n_c) x ceil(C_out / n_m)`` grid.  Copies
  follow rate synchronisation: ``min(dup_cap, round(pixels / pixels of
  the last conv))``, at least 1; the initiation interval is the slowest
  conv's ``ceil(pixels / copies)``.
* Placement: every layer's tiles are consecutive along a snake curve on
  the smallest square mesh that holds them all; a route is as long as
  the Manhattan distance between its tiles.
* Schedule: a layer whose padded width exceeds the 128-entry Rofm table
  runs in width strips (output columns in runs of ``(128 - K) // S + 1``,
  cut to a multiple of the pool stride), each streaming its own padded
  raster (the whole padded width where the layer is not cut).  Each
  strip's tiles fire once per output pixel: chain psums hop east within
  a group, each group's running sum goes south from its tail to the
  next group's tail, where it is pushed to and popped from the Rofm
  buffer; a psum packet holds 16 bits per output channel.  A strip runs
  its raster plus two cycles per chain tile, and every tile fetches one
  instruction per raster pixel.
* Tails: ReLU on every output of a plain conv, and after the shortcut
  add on a residual block's last conv (never on a projection); a
  ``K_p = S_p`` max-pool makes ``E * (F - F // S_p) * M`` compare events.
* Streams: one OFM transfer of the pre-pool output (8-bit activations)
  from each stage's tail to the next stage's head; the saved block input
  goes from its producer's tail to the projection's head and the
  projection's output to the add site, or straight to the add site;
  FC psum columns (16-bit) hop down the grid.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

from bench.reference import Layer, layers_of

#: entries of the per-tile Rofm schedule table (paper Tab. 3)
TABLE_CAPACITY = 128
#: partial sums travel the NoC at 16 bits (paper Tab. 3)
PSUM_BYTES = 2

SIM_FIELDS = ("macs", "chain_hops", "group_hops", "buf_push", "buf_pop",
              "act_ops", "pool_ops", "cycles", "instr_fetches")


def _chain(layer: Layer, n_c: int) -> Tuple[int, int]:
    """(tiles per group, tiles per chain) of a convolution."""
    if layer.c <= n_c:
        pack, splits = min(layer.k, max(1, n_c // layer.c)), 1
    else:
        pack, splits = 1, math.ceil(layer.c / n_c)
    group = math.ceil(layer.k / pack) * splits
    return group, layer.k * group


def copies(layers: Sequence[Layer], dup_cap: int) -> List[int]:
    """Weight copies per layer under rate synchronisation."""
    last = [l for l in layers if l.kind == "conv"][-1]
    base = last.e * last.f
    return [max(1, min(dup_cap, round(l.e * l.f / base)))
            if l.kind == "conv" else 1 for l in layers]


def initiation_interval(cfg: dict) -> int:
    layers = layers_of(cfg)
    return max(math.ceil(l.e * l.f / d)
               for l, d in zip(layers, copies(layers, cfg["dup_cap"]))
               if l.kind == "conv")


def _strip_widths(layer: Layer) -> List[int]:
    """Output columns per strip (one strip where the raster fits)."""
    if layer.w + 2 * layer.p <= TABLE_CAPACITY:
        return [layer.f]
    most = (TABLE_CAPACITY - layer.k) // layer.s + 1
    if layer.pool_s:
        most -= most % layer.pool_s
    return [min(most, layer.f - f0) for f0 in range(0, layer.f, most)]


class _Tally:
    """One frame's counters, accumulated stage by stage."""

    def __init__(self, cfg: dict):
        self.layers = layers_of(cfg)
        self.n_c, self.n_m = cfg["n_c"], cfg["n_m"]
        dup = copies(self.layers, cfg["dup_cap"])
        self.start: List[int] = []
        cursor = 0
        for layer, d in zip(self.layers, dup):
            self.start.append(cursor)
            cursor += self._tiles(layer) * d
        self.end = [s - 1 for s in self.start[1:]] + [cursor - 1]
        self.side = math.isqrt(cursor - 1) + 1
        self.sim = dict.fromkeys(SIM_FIELDS, 0)
        self.traffic: Dict[str, Dict[str, int]] = {
            "byte_hops": {}, "packets": {}, "hops": {}}

    def _tiles(self, layer: Layer) -> int:
        if layer.kind == "fc":
            return (math.ceil(layer.c_in / self.n_c)
                    * math.ceil(layer.c_out / self.n_m))
        return _chain(layer, self.n_c)[1] * math.ceil(layer.m / self.n_m)

    def _xy(self, t: int) -> Tuple[int, int]:
        r, c = divmod(t, self.side)
        return r, (self.side - 1 - c if r % 2 else c)

    def hops(self, a: int, b: int) -> int:
        (r1, c1), (r2, c2) = self._xy(a), self._xy(b)
        return abs(r1 - r2) + abs(c1 - c2)

    def send(self, kind: str, a: int, b: int, nbytes: int,
             count: int = 1) -> int:
        h = self.hops(a, b)
        for key, add in (("packets", count), ("hops", count * h),
                         ("byte_hops", count * h * nbytes)):
            d = self.traffic[key]
            d[kind] = d.get(kind, 0) + add
        return h

    def conv(self, li: int) -> None:
        layer, sim = self.layers[li], self.sim
        group, chain = _chain(layer, self.n_c)
        base = self.start[li]
        plain = layer.residual_from is None and not layer.shortcut
        psum = layer.m * PSUM_BYTES
        strips = _strip_widths(layer)
        for fs in strips:
            fires = layer.e * fs
            width = layer.w + 2 * layer.p if len(strips) == 1 \
                else (fs - 1) * layer.s + layer.k
            raster = (layer.h + 2 * layer.p) * width
            sim["cycles"] += raster + 2 * chain
            sim["instr_fetches"] += chain * raster
            sim["macs"] += fires * layer.k * layer.k * layer.c * layer.m
            sim["buf_push"] += (layer.k - 1) * fires
            sim["buf_pop"] += (layer.k - 1) * fires
            if plain:
                sim["act_ops"] += fires * layer.m
            if layer.pool_s:
                sim["pool_ops"] += (layer.e * (fs - fs // layer.pool_s)
                                    * layer.m)
            for i in range(layer.k):
                for u in range(group - 1):
                    t = base + i * group + u
                    h = self.send("chain", t, t + 1, psum, fires)
                    sim["chain_hops"] += fires * max(1, h)
                if i < layer.k - 1:
                    t = base + i * group + group - 1
                    h = self.send("group", t, t + group, psum, fires)
                    sim["group_hops"] += fires * max(1, h)

    def fc(self, li: int) -> None:
        layer = self.layers[li]
        rows = math.ceil(layer.c_in / self.n_c)
        cols = math.ceil(layer.c_out / self.n_m)
        base = self.start[li]
        self.sim["macs"] += layer.c_in * layer.c_out
        for j in range(cols):
            width = min(self.n_m, layer.c_out - j * self.n_m)
            for i in range(rows - 1):
                self.sim["chain_hops"] += self.send(
                    "split", base + i * cols + j, base + (i + 1) * cols + j,
                    width * PSUM_BYTES)
        if li < len(self.layers) - 1:
            self.sim["act_ops"] += layer.c_out

    def out_bytes(self, li: int) -> int:
        layer = self.layers[li]
        return layer.e * layer.f * layer.m if layer.kind == "conv" \
            else layer.c_out

    def frame(self) -> None:
        layers = self.layers
        block_inputs = {l.residual_from for l in layers if l.residual_from}
        saved: Dict[str, Tuple[Optional[int], int]] = {}
        stages: List[Tuple[int, Optional[int]]] = []
        li = 0
        while li < len(layers):
            nxt = li + 1 < len(layers) and layers[li + 1].shortcut
            sc = li + 1 if layers[li].residual_from and nxt else None
            stages.append((li, sc))
            li += 2 if sc is not None else 1
        prev: Optional[int] = None
        for s, (li, sc) in enumerate(stages):
            layer = layers[li]
            if layer.kind == "fc":
                self.fc(li)
            else:
                if layer.name in block_inputs:
                    saved[layer.name] = (prev, layer.h * layer.w * layer.c)
                self.conv(li)
                if layer.residual_from is not None:
                    src, nbytes = saved.pop(layer.residual_from)
                    if sc is not None:
                        if src is not None:
                            self.send("residual", self.end[src],
                                      self.start[sc], nbytes)
                        self.conv(sc)
                        self.send("residual", self.end[sc], self.end[li],
                                  self.out_bytes(sc))
                    elif src is not None:
                        self.send("residual", self.end[src], self.end[li],
                                  nbytes)
                    self.sim["act_ops"] += layer.e * layer.f * layer.m
            if s + 1 < len(stages):
                self.send("ofm", self.end[li], self.start[stages[s + 1][0]],
                          self.out_bytes(li))
            prev = li


def frame_counters(cfg: dict) -> Dict[str, int]:
    """One frame's counters, flattened: ``sim.<field>`` and
    ``traffic.<kind>.<class>``."""
    tally = _Tally(cfg)
    tally.frame()
    return flatten(tally.sim, tally.traffic)


def flatten(sim: Dict[str, int], traffic: Dict[str, Dict[str, int]]
            ) -> Dict[str, int]:
    out = {f"sim.{k}": int(v) for k, v in sim.items()}
    for kind, per_class in traffic.items():
        for cls, v in per_class.items():
            out[f"traffic.{kind}.{cls}"] = int(v)
    return out
