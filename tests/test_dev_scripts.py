"""The hand-run scripts of `dev/` that put their own functions in place of the
program's private names: a rename in the program fails here, on the CPU tier,
and not in a chip session."""

import importlib.util
import inspect
import sys
from pathlib import Path

DEV = Path(__file__).resolve().parents[1] / "dev"


def load(name: str):
    spec = importlib.util.spec_from_file_location(f"dev_{name}", DEV / f"{name}.py")
    module = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_check_faults_patches_names_the_program_has():
    """Every (module, name) a case of `dev/check_faults.py` replaces exists
    (the script reads each at import: a missing one raises there), is one the
    script restores after the case, and takes the arguments its stand-in
    takes, where the stand-in names them."""
    script = load("check_faults")
    from langstream_tpu.models import transformer as T

    assert all(hasattr(T, name) for name in script.PATCHED[T])
    assert set(script.FAMILIES) == {"keye", "glm", "kimi", "dots3", "lfm2", "sdar"}
    for family, fam in script.FAMILIES.items():
        assert "sound" in fam.cases and fam.cases["sound"] == script.Case()
        assert set(fam.controls or ()) <= set(fam.cases)
        for case, fault in fam.cases.items():
            assert set(fault.patch) <= set(script.SOUND), (family, case)
            for at, put in fault.patch.items():
                sound = script.SOUND[at]
                if not (inspect.isfunction(put) and inspect.isfunction(sound)):
                    continue  # a value, a property, or made from the run
                theirs, ours = (list(inspect.signature(f).parameters.values()) for f in (sound, put))
                if any(p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD) for p in ours):
                    continue
                # (an argument the stand-in leaves out is one its model never passes)
                rest = theirs[len(ours):]
                assert [p.name for p in ours] == [p.name for p in theirs[: len(ours)]] and all(
                    p.default is not p.empty for p in rest
                ), (family, case, at[1])
