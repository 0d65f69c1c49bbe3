"""Persist compiled programs so compilation is paid once per model.

Layout mirrors ``checkpoint/checkpointer.py``: one ``.npy`` per array plus
a fsynced ``program.json`` manifest, written into a ``.tmp`` directory and
``os.replace``d only when complete, so a crashed writer never leaves a
half-written program that a loader would pick up.  The round trip is
bit-exact: every array is stored verbatim (float payloads as fp32,
quantized payloads as int8 with their fp32 row-group scales, index
streams as int32/int64).

The manifest (format 6) holds the lowering geometry (``block``,
``tile``, ``precision``, ``cell_bits``), the chain config, and per conv
its payload files, its searched ``mapping``
(:meth:`~repro.core.mapping.MappingCandidate.to_manifest`, null for the
fixed scheme), its ``patch_order`` (``lowering.patch_order``, the row
order its weights were lowered in and its patches must be built in) and
its ``in_order`` payload (the channel order of the tensor its weight
rows read); the FC carries its ``reorder`` tag and ``in_order`` too.  A
``CompiledNetwork.partition`` (``engine/partition.py``) rides along when
set, so a program partitioned for an N-chip mesh reloads ready to serve
from one, and so does the range ``certificate``
(:class:`~repro.analysis.ranges.RangeCertificate`), of which only the
structure is checked here (M003): whether it still matches the payloads
is the certification pass's job (V506).  Any other ``format_version`` is
refused (M002): recompile such a program and save it again.

Only chain programs (``CNNConfig``: stride-1 convs with ``channel_norm``,
ReLU and optional 2x2 max pools) have a manifest; saving a graph program
(a ResNet's strides, residual adds, folded batch norm, other pools; a
MobileNetV2's grouped convs and ReLU6) raises ``NotImplementedError``.
"""

from __future__ import annotations

import json
import os
import shutil

import jax.numpy as jnp
import numpy as np

from repro.analysis.diagnostics import ProgramFormatError
from repro.core.mapping import MappingCandidate
from repro.core.sparse import BlockPatternWeight
from repro.engine.partition import NetworkPartition
from repro.engine.program import CompiledConv, CompiledFC, CompiledNetwork
from repro.models.cnn import CNNConfig

__all__ = [
    "save_program",
    "load_program",
    "read_manifest",
    "validate_manifest",
    "ProgramFormatError",
]

_MANIFEST = "program.json"
_FORMAT_VERSION = 6
_SUPPORTED_VERSIONS = (_FORMAT_VERSION,)


def _save_array(directory: str, name: str, arr) -> str:
    fname = f"{name}.npy"
    with open(os.path.join(directory, fname), "wb") as f:
        np.save(f, np.asarray(arr))
        f.flush()
        os.fsync(f.fileno())
    return fname


def _bp_manifest(prefix: str, bp: BlockPatternWeight, directory: str) -> dict:
    fields = ["w_comp", "block_ids", "nnz", "new_order", "inv_order",
              "dict_masks"]
    if bp.w_scales is not None:
        fields.append("w_scales")
    return {
        "k_in": bp.k_in,
        "n_out": bp.n_out,
        "block": bp.block,
        "tile": bp.tile,
        "arrays": {
            field: _save_array(directory, f"{prefix}.{field}", getattr(bp, field))
            for field in fields
        },
    }


def _load_bp(entry: dict, directory: str) -> BlockPatternWeight:
    def arr(field):
        return np.load(os.path.join(directory, entry["arrays"][field]))

    has_scales = "w_scales" in entry["arrays"]
    return BlockPatternWeight(
        w_comp=jnp.asarray(arr("w_comp")),
        block_ids=jnp.asarray(arr("block_ids")),
        nnz=arr("nnz"),
        new_order=arr("new_order"),
        inv_order=arr("inv_order"),
        k_in=int(entry["k_in"]),
        n_out=int(entry["n_out"]),
        block=int(entry["block"]),
        tile=int(entry["tile"]),
        dict_masks=arr("dict_masks"),
        w_scales=jnp.asarray(arr("w_scales")) if has_scales else None,
    )


def _save_order(directory: str, name: str, order) -> str | None:
    if order is None:
        return None
    return _save_array(directory, f"{name}.in_order",
                       np.asarray(order, np.int32))


def _load_order(entry: dict, directory: str):
    fname = entry["in_order"]
    if fname is None:
        return None
    return np.load(os.path.join(directory, fname))


def save_program(directory: str, program: CompiledNetwork) -> str:
    """Atomically write ``program`` under ``directory``.  Returns the path.

    Raises ``NotImplementedError`` for a graph program (the manifest holds
    chains only)."""
    if not isinstance(program.config, CNNConfig):
        raise NotImplementedError(
            "save_program: the manifest format holds chain programs "
            f"(CNNConfig) only, not a {type(program.config).__name__} "
            "network: strides, src/residual edges, folded batch norm, "
            "3x3/2 pools, grouped convs and ReLU6 are not in it"
        )
    parent = os.path.dirname(os.path.abspath(directory))
    os.makedirs(parent, exist_ok=True)
    tmp = directory.rstrip("/") + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)

    cfg = program.config
    manifest = {
        "format_version": _FORMAT_VERSION,
        "block": program.block,
        "tile": program.tile,
        "precision": program.precision,
        "cell_bits": program.cell_bits,
        "config": {
            "conv_channels": [list(c) for c in cfg.conv_channels],
            "pool_after": sorted(cfg.pool_after),
            "num_classes": cfg.num_classes,
            "input_hw": cfg.input_hw,
            "kernel": cfg.kernel,
        },
        "convs": [],
    }
    if program.partition is not None:
        manifest["partition"] = program.partition.to_manifest()
    if getattr(program, "certificate", None) is not None:
        manifest["certificate"] = program.certificate.to_manifest()
    for c in program.convs:
        manifest["convs"].append(
            {
                "name": c.name,
                "c_in": c.c_in,
                "c_out": c.c_out,
                "kernel": c.kernel,
                "out_hw": c.out_hw,
                "pool_after": c.pool == "max2",
                "bias": _save_array(tmp, f"{c.name}.bias", c.bias),
                "pattern_bits": _save_array(
                    tmp, f"{c.name}.pattern_bits", c.pattern_bits
                ),
                "bp": _bp_manifest(c.name, c.bp, tmp),
                "mapping": (
                    None if c.mapping is None else c.mapping.to_manifest()
                ),
                "patch_order": c.patch_order,
                "in_order": _save_order(tmp, c.name, c.in_order),
            }
        )
    manifest["fc"] = {
        "d_in": program.fc.d_in,
        "d_out": program.fc.d_out,
        "bias": _save_array(tmp, "fc.bias", program.fc.bias),
        "bp": _bp_manifest("fc", program.fc.bp, tmp),
        "reorder": program.fc.reorder,
        "in_order": _save_order(tmp, "fc", program.fc.in_order),
    }
    with open(os.path.join(tmp, _MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    # never delete the previous program before the new one is in place:
    # move it aside, swap in the new directory, then drop the old copy
    old = directory.rstrip("/") + ".old"
    if os.path.exists(old):
        shutil.rmtree(old)
    if os.path.exists(directory):
        os.replace(directory, old)
    os.replace(tmp, directory)
    if os.path.exists(old):
        shutil.rmtree(old)
    return directory


def _resolve_directory(directory: str) -> str:
    """Fall back to ``<directory>.old`` when the target has no manifest —
    a save interrupted between the two swap renames leaves the previous
    complete program there, so a restarting service still has a model."""
    if not os.path.exists(os.path.join(directory, _MANIFEST)):
        old = directory.rstrip("/") + ".old"
        if os.path.exists(os.path.join(old, _MANIFEST)):
            return old
    return directory


def read_manifest(directory: str) -> dict:
    """Read the manifest JSON, raising :class:`ProgramFormatError` (M001)
    instead of an opaque OSError/JSONDecodeError."""
    path = os.path.join(_resolve_directory(directory), _MANIFEST)
    try:
        with open(path) as f:
            manifest = json.load(f)
    except OSError as e:
        raise ProgramFormatError(
            f"program manifest unreadable: {path}: {e}", rule="M001"
        ) from e
    except ValueError as e:
        raise ProgramFormatError(
            f"program manifest is not valid JSON: {path}: {e}", rule="M001"
        ) from e
    if not isinstance(manifest, dict):
        raise ProgramFormatError(
            f"program manifest is not a JSON object: {path}", rule="M001"
        )
    return manifest


_BP_ARRAY_FIELDS = ("w_comp", "block_ids", "nnz", "new_order", "inv_order",
                    "dict_masks")
_CONFIG_KEYS = ("conv_channels", "pool_after", "num_classes", "input_hw",
                "kernel")
_ROOT_KEYS = ("block", "tile", "precision", "cell_bits", "config", "convs",
              "fc")
_CONV_KEYS = ("name", "c_in", "c_out", "kernel", "out_hw", "pool_after",
              "bias", "pattern_bits", "bp", "mapping", "patch_order",
              "in_order")
_FC_KEYS = ("d_in", "d_out", "bias", "bp", "reorder", "in_order")
_MAPPING_KEYS = ("rows", "cols", "cells_per_weight", "ou_rows", "ou_cols",
                 "block_order", "reorder")
_CERT_KEYS = ("input_lo", "input_hi", "precision", "cell_bits",
              "fp32_safe", "layers")
_CERT_LAYER_KEYS = ("name", "pre_lo", "pre_hi", "act_lo", "act_hi")


def _require(entry: dict, keys, where: str) -> None:
    missing = [k for k in keys if k not in entry]
    if missing:
        raise ProgramFormatError(
            f"program manifest {where} is missing key(s) "
            f"{', '.join(missing)}", rule="M003"
        )


def _check_mapping_entry(entry, where: str) -> None:
    """Structural (M003) check of a conv's ``mapping`` entry.

    Only types and keys are checked here — *validity* of the tags and
    dims against the packed operands is the static verifier's job
    (V205/V206), so a structurally sound but semantically corrupt save
    surfaces as a diagnostic after load, not a format error."""
    if entry is None:
        return
    if not isinstance(entry, dict):
        raise ProgramFormatError(
            f"program manifest {where} must be an object or null",
            rule="M003",
        )
    _require(entry, _MAPPING_KEYS, where)
    for k in ("rows", "cols", "cells_per_weight", "ou_rows", "ou_cols"):
        if not isinstance(entry[k], int) or isinstance(entry[k], bool):
            raise ProgramFormatError(
                f"program manifest {where}.{k} must be an integer",
                rule="M003",
            )
    for k in ("block_order", "reorder"):
        if not isinstance(entry[k], str):
            raise ProgramFormatError(
                f"program manifest {where}.{k} must be a string",
                rule="M003",
            )


def _check_certificate_entry(entry, where: str) -> None:
    """Structural (M003) check of the range ``certificate`` entry.

    Like :func:`_check_mapping_entry`, only keys and types are enforced
    here — whether the certified bounds and cell table still match the
    payloads is the certification pass's V506, so a structurally sound
    but stale certificate surfaces as a diagnostic after load."""
    if entry is None:
        return
    if not isinstance(entry, dict):
        raise ProgramFormatError(
            f"program manifest {where} must be an object or null",
            rule="M003",
        )
    _require(entry, _CERT_KEYS, where)
    for k in ("input_lo", "input_hi"):
        if not isinstance(entry[k], (int, float)) or isinstance(
            entry[k], bool
        ):
            raise ProgramFormatError(
                f"program manifest {where}.{k} must be a number",
                rule="M003",
            )
    if not isinstance(entry["precision"], str):
        raise ProgramFormatError(
            f"program manifest {where}.precision must be a string",
            rule="M003",
        )
    if not isinstance(entry["cell_bits"], int) or isinstance(
        entry["cell_bits"], bool
    ):
        raise ProgramFormatError(
            f"program manifest {where}.cell_bits must be an integer",
            rule="M003",
        )
    layers = entry["layers"]
    if not isinstance(layers, list):
        raise ProgramFormatError(
            f"program manifest {where}.layers must be a list", rule="M003"
        )
    for i, e in enumerate(layers):
        lwhere = f"{where}.layers[{i}]"
        if not isinstance(e, dict):
            raise ProgramFormatError(
                f"program manifest {lwhere} must be an object", rule="M003"
            )
        _require(e, _CERT_LAYER_KEYS, lwhere)
        mc = e.get("min_cells")
        if mc is not None and not isinstance(mc, list):
            raise ProgramFormatError(
                f"program manifest {lwhere}.min_cells must be a list or "
                "null", rule="M003"
            )


def _check_order_entry(entry: dict, directory: str, where: str) -> None:
    """An ``in_order``: null (channel order) or an existing payload;
    whether it is a permutation is the verifier's V208."""
    fname = entry["in_order"]
    if fname is None:
        return
    if not isinstance(fname, str) or not os.path.exists(
        os.path.join(directory, fname)
    ):
        raise ProgramFormatError(
            f"payload file for {where}.in_order missing: {fname!r}",
            rule="M004",
        )


def _check_bp_entry(entry: dict, directory: str, where: str) -> None:
    if not isinstance(entry, dict):
        raise ProgramFormatError(
            f"program manifest {where} must be an object", rule="M003"
        )
    _require(entry, ("k_in", "n_out", "block", "tile", "arrays"), where)
    arrays = entry["arrays"]
    if not isinstance(arrays, dict):
        raise ProgramFormatError(
            f"program manifest {where}.arrays must be an object", rule="M003"
        )
    _require(arrays, _BP_ARRAY_FIELDS, f"{where}.arrays")
    for field, fname in arrays.items():
        if not isinstance(fname, str) or not os.path.exists(
            os.path.join(directory, fname)
        ):
            raise ProgramFormatError(
                f"payload file for {where}.arrays.{field} missing: "
                f"{fname!r}", rule="M004"
            )


def validate_manifest(manifest: dict, directory: str) -> None:
    """Validate manifest version, keys, and payload files *before* any
    array is constructed.  Raises :class:`ProgramFormatError` on the
    first problem; returns None when the manifest is loadable."""
    directory = _resolve_directory(directory)
    version = manifest.get("format_version")
    if version not in _SUPPORTED_VERSIONS:
        raise ProgramFormatError(
            f"unsupported program format version {version!r} "
            f"(supported: {_SUPPORTED_VERSIONS}); recompile the program "
            "with compile_network and save it again", rule="M002"
        )
    _require(manifest, _ROOT_KEYS, "root")
    cfg = manifest["config"]
    if not isinstance(cfg, dict):
        raise ProgramFormatError(
            "program manifest config must be an object", rule="M003"
        )
    _require(cfg, _CONFIG_KEYS, "config")
    convs = manifest["convs"]
    if not isinstance(convs, list):
        raise ProgramFormatError(
            "program manifest convs must be a list", rule="M003"
        )
    if manifest["precision"] not in ("fp32", "int8"):
        raise ProgramFormatError(
            f"unknown precision {manifest['precision']!r}", rule="M003"
        )
    for i, e in enumerate(convs):
        where = f"convs[{i}]"
        if not isinstance(e, dict):
            raise ProgramFormatError(
                f"program manifest {where} must be an object", rule="M003"
            )
        _require(e, _CONV_KEYS, where)
        for field in ("bias", "pattern_bits"):
            fname = e[field]
            if not isinstance(fname, str) or not os.path.exists(
                os.path.join(directory, fname)
            ):
                raise ProgramFormatError(
                    f"payload file for {where}.{field} missing: "
                    f"{fname!r}", rule="M004"
                )
        _check_bp_entry(e["bp"], directory, f"{where}.bp")
        _check_mapping_entry(e["mapping"], f"{where}.mapping")
        # which orders are valid, and where, is the verifier's V207
        if not isinstance(e["patch_order"], str):
            raise ProgramFormatError(
                f"program manifest {where}.patch_order must be a string",
                rule="M003",
            )
        _check_order_entry(e, directory, where)
    fce = manifest["fc"]
    if not isinstance(fce, dict):
        raise ProgramFormatError(
            "program manifest fc must be an object", rule="M003"
        )
    _require(fce, _FC_KEYS, "fc")
    if not isinstance(fce["reorder"], str):
        raise ProgramFormatError(
            "program manifest fc.reorder must be a string", rule="M003"
        )
    fname = fce["bias"]
    if not isinstance(fname, str) or not os.path.exists(
        os.path.join(directory, fname)
    ):
        raise ProgramFormatError(
            f"payload file for fc.bias missing: {fname!r}", rule="M004"
        )
    _check_bp_entry(fce["bp"], directory, "fc.bp")
    _check_order_entry(fce, directory, "fc")
    part = manifest.get("partition")
    if part is not None:
        _require(part, ("data", "model", "data_axis", "model_axis"),
                 "partition")
    _check_certificate_entry(manifest.get("certificate"), "certificate")


def load_program(directory: str, verify: bool = True) -> CompiledNetwork:
    """Load a program previously written by :func:`save_program`.

    The manifest's version, keys, and payload files are validated
    *before* any array is constructed — a corrupt or truncated program
    raises one clear :class:`ProgramFormatError` instead of an opaque
    ``KeyError`` mid-load.  With ``verify=True`` (the default: saved
    programs are an untrusted input) the loaded network additionally
    runs the full static verifier and a
    :class:`~repro.analysis.diagnostics.VerificationError` carries the
    diagnostic report.  Pass ``verify=False`` on hot paths that reload
    programs this process just saved.
    """
    directory = _resolve_directory(directory)
    manifest = read_manifest(directory)
    validate_manifest(manifest, directory)
    c = manifest["config"]
    cfg = CNNConfig(
        conv_channels=tuple(tuple(x) for x in c["conv_channels"]),
        pool_after=frozenset(c["pool_after"]),
        num_classes=c["num_classes"],
        input_hw=c["input_hw"],
        kernel=c["kernel"],
    )
    try:
        convs = [
            CompiledConv(
                name=e["name"],
                c_in=e["c_in"],
                c_out=e["c_out"],
                kernel=e["kernel"],
                out_hw=e["out_hw"],
                pool="max2" if e["pool_after"] else None,
                bp=_load_bp(e["bp"], directory),
                bias=np.load(os.path.join(directory, e["bias"])),
                pattern_bits=np.load(
                    os.path.join(directory, e["pattern_bits"])
                ),
                mapping=(
                    MappingCandidate.from_manifest(e["mapping"])
                    if e["mapping"] is not None
                    else None
                ),
                patch_order=e["patch_order"],
                in_order=_load_order(e, directory),
            )
            for e in manifest["convs"]
        ]
        fce = manifest["fc"]
        fc = CompiledFC(
            d_in=fce["d_in"],
            d_out=fce["d_out"],
            bp=_load_bp(fce["bp"], directory),
            bias=np.load(os.path.join(directory, fce["bias"])),
            reorder=fce["reorder"],
            in_order=_load_order(fce, directory),
        )
    except (OSError, ValueError) as e:
        raise ProgramFormatError(
            f"program payload under {directory} failed to load: {e}",
            rule="M005",
        ) from e
    part = manifest.get("partition")
    cert_entry = manifest.get("certificate")
    certificate = None
    if cert_entry is not None:
        # lazy: diagnostics-only dependency, keeps the load path's
        # import graph free of the analysis interpreter
        from repro.analysis.ranges import RangeCertificate

        try:
            certificate = RangeCertificate.from_manifest(cert_entry)
        except (KeyError, TypeError, ValueError) as e:
            raise ProgramFormatError(
                f"program manifest certificate failed to decode: {e}",
                rule="M003",
            ) from e
    program = CompiledNetwork(
        config=cfg,
        convs=convs,
        fc=fc,
        block=manifest["block"],
        tile=manifest["tile"],
        partition=NetworkPartition.from_manifest(part) if part else None,
        precision=manifest["precision"],
        cell_bits=int(manifest["cell_bits"]),
        certificate=certificate,
    )
    if verify:
        from repro.analysis.verify import verify_network

        verify_network(program).raise_if_errors(
            f"load_program({directory!r})"
        )
    return program
