"""Writes ``tiny_trace.pbtxt``, the hand-built XSpace the trace tests
read. Two devices and one host thread, times in nanoseconds from
1000 ns:

device 0, "XLA Ops":  while.1 [0, 100) holding fusion.1 [10, 30) and
                      flash_fwd [30, 50); all-reduce.1 [120, 150);
                      fusion.2 [140, 160); paged_decode [200, 230)
device 1, "XLA Ops":  fusion.1 [0, 40); all-reduce.1 [40, 100)
host:                 traced_window [0, 300); step_dispatch [0, 110);
                      block [110, 190); make_batch [160, 200);
                      in_flight [100, 260)
"""

import os

DEV0 = [("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 100),
        ("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", 10, 20),
        ('%custom-call.1 = bf16[8]{0} custom-call(%q), custom_call_target='
         '"tpu_custom_call", backend_config={"kernel_name":"flash_fwd"}',
         30, 20),
        ("%all-reduce.1 = f32[4]{0} all-reduce(%g), replica_groups={}",
         120, 30),
        ("%fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop", 140, 20),
        ('%custom-call.2 = bf16[8]{0} custom-call(%q), custom_call_target='
         '"tpu_custom_call", backend_config={"kernel_name":"paged_decode"}',
         200, 30)]
DEV1 = [("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", 0, 40),
        ("%all-reduce.1 = f32[4]{0} all-reduce(%g), replica_groups={}",
         40, 60)]
HOST = [("bench:traced_window", 0, 300), ("bench:step_dispatch", 0, 110),
        ("bench:block", 110, 80), ("bench:make_batch", 160, 40),
        ("bench:in_flight", 100, 160), ("$not_ours", 0, 5)]


def plane(pid, name, line, events):
    meta, rows = {}, []
    for ev_name, start, dur in events:
        mid = meta.setdefault(ev_name, len(meta) + 1)
        rows.append(f"    events {{ metadata_id: {mid} offset_ps: "
                    f"{start * 1000} duration_ps: {dur * 1000} }}")
    metas = "\n".join(
        f"  event_metadata {{ key: {mid} value {{ id: {mid} name: "
        f"{quote(n)} }} }}" for n, mid in meta.items())
    return (f"planes {{\n  id: {pid}\n  name: \"{name}\"\n  lines {{\n"
            f"    id: 1\n    name: \"{line}\"\n    timestamp_ns: 1000\n"
            + "\n".join(rows) + "\n  }\n" + metas + "\n}\n")


def quote(s):
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


if __name__ == "__main__":
    text = (plane(1, "/device:TPU:0", "XLA Ops", DEV0)
            + plane(2, "/device:TPU:1", "XLA Ops", DEV1)
            + plane(3, "/host:CPU", "python", HOST))
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "tiny_trace.pbtxt"), "w") as f:
        f.write(text)
