"""Aggregate per-op device time from a captured xplane trace."""
import glob, re, sys, collections
from tensorflow.tsl.profiler.protobuf import xplane_pb2

f = sorted(glob.glob('/tmp/jaxprof/**/*.xplane.pb', recursive=True))[-1]
xs = xplane_pb2.XSpace()
xs.ParseFromString(open(f, 'rb').read())

for plane in xs.planes:
    if 'TPU' not in plane.name and 'device' not in plane.name.lower():
        continue
    print('== PLANE:', plane.name)
    evmeta = plane.event_metadata
    agg = collections.Counter()
    total = 0
    for line in plane.lines:
        if 'XLA Ops' not in line.name and 'Steps' not in line.name:
            pass
        for ev in line.events:
            name = evmeta[ev.metadata_id].name
            dur = ev.duration_ps / 1e6   # us
            # bucket by op kind: strip fusion numbering
            kind = re.sub(r'[.\d]+$', '', name)
            agg[(line.name, kind)] += dur
    top = agg.most_common(40)
    for (lname, kind), us in top:
        print(f'{lname:20s} {kind:60s} {us/5:10.1f} us/step')
