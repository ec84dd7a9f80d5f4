"""decode_step_device_ms: model step (``models/model.py`` via
``train/step.py``).

Mean device time of one run of the paged decode program
(``paged_decode_step``) in the traced window, in ms.
"""


def read(tr):
    runs = tr.program_events("decode")
    if not runs:
        return None
    return sum(e.dur for e in runs) / len(runs) * 1e-6
