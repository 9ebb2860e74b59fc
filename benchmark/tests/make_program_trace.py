"""Writes ``program_trace.pbtxt``, the hand-built XSpace on which the
readers of ``program_trace.py`` are checked by hand arithmetic. One
device, one host thread, times in nanoseconds from 1000 ns.

device 0, "XLA Ops" (name [start, end) scope path):
    while.1      [0, 100)    jit(p2t_train_step)/jvp(blocks)/while
      fusion.1   [10, 30)    attn/norm/reduce_sum
      flash_fwd.1 [30, 50)   attn/jit(flash_bshd)/flash_fwd/pallas_call
      fusion.2   [50, 80)    checkpoint/rematted_computation/mlp/dot_general
    fusion.3     [120, 150)  jit(p2t_train_step)/transpose(jvp(head_ce))/dot_general
    fused_adamw.1 [150, 170) (no path: a named kernel under no scope)
    fusion.4     [170, 200)  jit(p2t_train_step)/optimizer/mul
    copy.7       [220, 250)  (no path)
  self time: attn 40 (fwd), mlp 30 (recomputed), head_ce 30 (bwd),
  optimizer 30, kernel 20, blocks 30 (while.1's own: the scan's
  plumbing), unscoped 30 (copy.7);
  busy 210 = [0,100) + [120,200) + [220,250); idle gaps [100,120),
  [200,220), [250,300)
device 0, "XLA Modules": jit_p2t_train_step(123) [0, 200),
    jit_p2t_kv_scatter_prefill(77) [220, 250)
host: bench:traced_window [0, 300); bench:in_flight [100, 300);
    p2t:train.step [0, 40) built=1 and [40, 100) built=0;
    p2t:submit [98, 99) req=0;
    p2t:decode [100, 220) holding decode.select [100, 105),
      decode.build_batch [105, 110), decode.dispatch [110, 150) (rows=2
      row_bucket=4 page_bucket=8 ctx_tokens=900 blocks_in_use=3
      blocks_total=4 evicted=0), decode.readback [150, 200),
      decode.emit [200, 220);
    p2t:admit [225, 255) holding admit.schedule [225, 227)
      and prefill [227, 255) req=0 tokens=500 padded=512 holding
      prefill.dispatch [227, 240) (holding build [229, 235)
      program=prefill sig=512 and in it build.cost [233, 235)),
      prefill.readback [240, 248), prefill.scatter [248, 255): the
      first token of req 0 is on the host 248 - 98 = 150 after its
      submit began, and 12 of 512 computed positions were padding;
    p2t:decode [260, 262) holding decode.select [260, 262) only: a
      tick with nothing ready
  idle by innermost span, instant by instant: [100,120) -> decode.select
  5, decode.build_batch 5, decode.dispatch 10; [200,220) -> decode.emit
  20; [250,300) -> prefill.scatter 5, decode.select 2, none 43
"""

import os

from make_tiny_trace import quote

OPS = [
    ("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 100,
     "jit(p2t_train_step)/jvp(blocks)/while"),
    ("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", 10, 20,
     "attn/norm/reduce_sum"),
    ('%flash_fwd.1 = (bf16[8]{0}, f32[8]{0}) custom-call(%q), '
     'custom_call_target="tpu_custom_call"', 30, 20,
     "attn/jit(flash_bshd)/flash_fwd/pallas_call"),
    ("%fusion.2 = bf16[8]{0} fusion(%p), kind=kOutput", 50, 30,
     "checkpoint/rematted_computation/mlp/dot_general"),
    ("%fusion.3 = bf16[8]{0} fusion(%p), kind=kOutput", 120, 30,
     "jit(p2t_train_step)/transpose(jvp(head_ce))/dot_general"),
    ('%fused_adamw.1 = (f32[8]{0}) custom-call(%q), '
     'custom_call_target="tpu_custom_call"', 150, 20, ""),
    ("%fusion.4 = f32[8]{0} fusion(%p), kind=kLoop", 170, 30,
     "jit(p2t_train_step)/optimizer/mul"),
    ("%copy.7 = bf16[8]{0} copy(%p)", 220, 30, ""),
]
MODULES = [("jit_p2t_train_step(123)", 0, 200, ""),
           ("jit_p2t_kv_scatter_prefill(77)", 220, 30, "")]
HOST = [
    ("bench:traced_window", 0, 300, {}),
    ("bench:in_flight", 100, 200, {}),
    ("p2t:train.step", 0, 40, {"built": 1}),
    ("p2t:train.step", 40, 60, {"built": 0}),
    ("p2t:submit", 98, 1, {"req": 0}),
    ("p2t:decode", 100, 120, {}),
    ("p2t:decode.select", 100, 5, {}),
    ("p2t:decode.build_batch", 105, 5, {}),
    ("p2t:decode.dispatch", 110, 40,
     {"rows": 2, "row_bucket": 4, "page_bucket": 8, "ctx_tokens": 900,
      "blocks_in_use": 3, "blocks_total": 4, "evicted": 0}),
    ("p2t:decode.readback", 150, 50, {}),
    ("p2t:decode.emit", 200, 20, {}),
    ("p2t:admit", 225, 30, {}),
    ("p2t:admit.schedule", 225, 2, {}),
    ("p2t:prefill", 227, 28, {"req": 0, "tokens": 500, "padded": 512}),
    ("p2t:prefill.dispatch", 227, 13, {}),
    ("p2t:build", 229, 6, {"program": "prefill", "sig": "512"}),
    ("p2t:build.cost", 233, 2, {}),
    ("p2t:prefill.readback", 240, 8, {}),
    ("p2t:prefill.scatter", 248, 7, {}),
    ("p2t:decode", 260, 2, {}),
    ("p2t:decode.select", 260, 2, {}),
    ("$not_ours", 0, 5, {}),
]


class Plane:
    """One XPlane in text form. A host event's counts are stats of the
    EVENT; a device op's scope path is the ``tf_op`` stat of its event
    METADATA, "<path>:", as the chip's profiler writes it."""

    def __init__(self, pid, name):
        self.pid, self.name = pid, name
        self.meta, self.meta_stats = {}, {}
        self.stat_meta, self.lines = {}, []

    def _stat(self, key, val):
        sid = self.stat_meta.setdefault(key, len(self.stat_meta) + 1)
        return (f" stats {{ metadata_id: {sid} str_value: {quote(val)} }}"
                if isinstance(val, str) else
                f" stats {{ metadata_id: {sid} int64_value: {val} }}")

    def line(self, lid, name, events):
        rows = []
        for ev_name, start, dur, stats in events:
            mid = self.meta.setdefault(ev_name, len(self.meta) + 1)
            st = ""
            if isinstance(stats, str):      # a device op's scope path
                if stats:
                    self.meta_stats[mid] = self._stat("tf_op", stats + ":")
            else:
                st = "".join(self._stat(k, v) for k, v in stats.items())
            rows.append(f"    events {{ metadata_id: {mid} offset_ps: "
                        f"{start * 1000} duration_ps: {dur * 1000}{st} }}")
        self.lines.append(
            f"  lines {{\n    id: {lid}\n    name: \"{name}\"\n"
            f"    timestamp_ns: 1000\n" + "\n".join(rows) + "\n  }\n")

    def text(self):
        metas = "".join(
            f"  event_metadata {{ key: {mid} value {{ id: {mid} name: "
            f"{quote(n)}{self.meta_stats.get(mid, '')} }} }}\n"
            for n, mid in self.meta.items())
        stats = "".join(
            f"  stat_metadata {{ key: {sid} value {{ id: {sid} name: "
            f"{quote(n)} }} }}\n" for n, sid in self.stat_meta.items())
        return (f"planes {{\n  id: {self.pid}\n  name: \"{self.name}\"\n"
                + "".join(self.lines) + metas + stats + "}\n")


if __name__ == "__main__":
    dev = Plane(1, "/device:TPU:0")
    dev.line(1, "XLA Ops", OPS)
    dev.line(2, "XLA Modules", MODULES)
    host = Plane(2, "/host:CPU")
    host.line(1, "python", HOST)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "program_trace.pbtxt"), "w") as f:
        f.write(dev.text() + host.text())
