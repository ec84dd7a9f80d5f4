"""prefill_chunk_device_ms: model step (``models/model.py`` via
``train/step.py``).

Mean device time of one run of the prefill-chunk program
(``prefill_chunk_step``) in the traced window, in ms.
"""


def read(tr):
    runs = tr.program_events("prefill_chunk")
    if not runs:
        return None
    return sum(e.dur for e in runs) / len(runs) * 1e-6
