"""Record ``cpu_regions.xplane.pb`` and ``cpu_regions.spans.jsonl``: a small
trace of the served path with the program's own regions in it.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/testdata/record_regions.py

A toy ``RealEngine`` (the smollm block at the program's ``.reduced()``
widths) behind ``InProcessBackend`` and the loopback sidecar with a
flight recorder attached serves three streamed requests sent at once,
inside the harness's window span: the trace holds the ``clairvoyant.*``
regions of the engine's worker thread and of the event loop, and the
spans file the recorder's spans of the same requests (its JSONL export).
"""

import asyncio
import glob
import os
import shutil
import sys
import tempfile

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from repro.configs import get_config  # noqa: E402
from repro.serving.backends import HTTPBackend, InProcessBackend  # noqa: E402
from repro.serving.engine import RealEngine  # noqa: E402
from repro.serving.http_sidecar import Sidecar  # noqa: E402
from repro.serving.observability import Observability  # noqa: E402
from repro.serving.server import ClairvoyantServer  # noqa: E402

PROMPTS = ["a short question", "tell me a story about a cat " * 2,
           "one more prompt here"]
TOKENS = [7, 10, 6]


async def serve(trace_dir: str):
    eng = RealEngine(get_config("smollm-360m").reduced(), max_len=64,
                     segment_len=4)
    srv = ClairvoyantServer(policy="fcfs", predictor=None,
                            engines=[InProcessBackend(eng)], seed=0,
                            deadline_mode="sojourn",
                            observability=Observability.default())
    sc = Sidecar(srv, port=0, max_new_tokens=16)
    await sc.start()
    client = HTTPBackend("127.0.0.1", sc.port)

    async def send_all():
        return await asyncio.gather(*[
            client.generate(p, max_new_tokens=n, on_segment=lambda d: None)
            for p, n in zip(PROMPTS, TOKENS)])

    await send_all()                       # compile outside the trace
    srv.obs.recorder.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        await asyncio.sleep(0.01)
        await send_all()
        await asyncio.sleep(0.01)
    jax.profiler.stop_trace()
    await sc.shutdown(drain_s=2.0)
    srv.obs.recorder.write_jsonl(os.path.join(HERE, "cpu_regions.spans.jsonl"))


def main() -> None:
    tmp = tempfile.mkdtemp()
    asyncio.run(serve(tmp))
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(HERE, "cpu_regions.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
