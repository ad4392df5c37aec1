"""config-drift: the Config dataclass, its argparse overlay, and the
README flag docs must agree.

The flag surface is the product (reference-parity CLI, SURVEY.md §2
L6), and it drifts in four distinct ways, each of which has bitten a
round or would have:

  - dead flag: `add_argument` whose dest `load_from_args` never reads —
    the flag parses and silently does nothing;
  - phantom dest: `ns.X` read in `load_from_args` with no matching
    `add_argument` — AttributeError the first time that path runs;
  - unknown attr: `verify()` / any method touching `self.UPPERCASE`
    that is not a dataclass field — a typo'd guard that guards nothing;
  - doc drift: an argparse flag README never mentions, or a flag
    documented in README's knobs section that argparse no longer
    accepts.

Plus the completeness invariant: every UPPERCASE Config field is
either assigned from `ns.*` in `load_from_args` (CLI-reachable) or
listed in `CONFIG_CONSTANTS` (config.py's explicit no-CLI register) —
adding a new attr forces a conscious choice between a flag and a
documented constant.

README matching: a flag counts as documented if it appears ANYWHERE in
README.md (word-boundary match). The reverse direction (stale docs)
only polices fenced code blocks of sections whose heading mentions
"flags"/"knobs" — prose and tool-CLI examples (`--requests`, `--n`)
are other programs' surfaces.

All parsing is AST/text — config.py is never imported.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterable, List, Optional, Sequence, Set, Tuple

from tools.graftlint.core import FileContext, Finding, Rule, register

RULE = "config-drift"

_FLAG_RE = re.compile(r"(?<![\w-])--([A-Za-z][\w-]*)")
_HEADING_RE = re.compile(r"^#{2,3}\s")
_FLAG_SECTION_RE = re.compile(r"^#{2,3}\s.*\b(flags|knobs)\b",
                              re.IGNORECASE)


def _const_str_set(node: ast.AST) -> Optional[Set[str]]:
    """Literal str elements of a set/tuple/list/frozenset(...) node."""
    if isinstance(node, ast.Call) and getattr(
            node.func, "id", "") == "frozenset" and node.args:
        node = node.args[0]
    if isinstance(node, (ast.Set, ast.Tuple, ast.List)):
        out = set()
        for e in node.elts:
            if not (isinstance(e, ast.Constant)
                    and isinstance(e.value, str)):
                return None
            out.add(e.value)
        return out
    return None


class ConfigModel:
    """Everything config-drift needs, lifted from config.py's AST."""

    def __init__(self, tree: ast.Module):
        self.fields: Set[str] = set()          # UPPERCASE dataclass attrs
        self.constants: Set[str] = set()       # CONFIG_CONSTANTS entries
        self.flags: List[Tuple[str, int]] = []  # (--flag, line)
        self.dests: List[Tuple[str, int]] = []  # (dest, line)
        self.ns_reads: Set[str] = set()        # ns.X in load_from_args
        self.cfg_writes: Set[str] = set()      # cfg.X in load_from_args
        self.self_refs: List[Tuple[str, int]] = []  # self.UPPER anywhere
        self._walk(tree)

    def _walk(self, tree: ast.Module) -> None:
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                    getattr(t, "id", "") == "CONFIG_CONSTANTS"
                    for t in node.targets):
                self.constants = _const_str_set(node.value) or set()
            if isinstance(node, ast.ClassDef) and node.name == "Config":
                self._walk_config(node)

    def _walk_config(self, cls: ast.ClassDef) -> None:
        for item in cls.body:
            if isinstance(item, ast.AnnAssign) and isinstance(
                    item.target, ast.Name):
                name = item.target.id
                if name.isupper():
                    self.fields.add(name)
            elif isinstance(item, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                if item.name == "arguments_parser":
                    self._walk_parser(item)
                elif item.name == "load_from_args":
                    self._walk_loader(item)
                else:
                    self._walk_method(item)

    def _walk_parser(self, fn: ast.FunctionDef) -> None:
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call) and getattr(
                    node.func, "attr", "") == "add_argument"):
                continue
            # every spelling of the option is a flag argparse accepts
            # (`--block_config`, `--lfm_config`); its dest is the
            # first's, as argparse has it
            long_flags = [a.value for a in node.args
                          if isinstance(a, ast.Constant) and isinstance(
                              a.value, str) and a.value.startswith("--")]
            if not long_flags:
                continue  # short-only options have no doc contract
            long_flag = long_flags[0]
            self.flags.extend((f, node.lineno) for f in long_flags)
            dest = None
            for kw in node.keywords:
                if kw.arg == "dest" and isinstance(
                        kw.value, ast.Constant):
                    dest = kw.value.value
            if dest is None:
                dest = long_flag.lstrip("-").replace("-", "_")
            self.dests.append((dest, node.lineno))

    def _walk_loader(self, fn: ast.FunctionDef) -> None:
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and isinstance(
                    node.value, ast.Name):
                if node.value.id == "ns":
                    self.ns_reads.add(node.attr)
                elif node.value.id == "cfg" and isinstance(
                        node.ctx, ast.Store):
                    self.cfg_writes.add(node.attr)

    def _walk_method(self, fn: ast.FunctionDef) -> None:
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and isinstance(
                    node.value, ast.Name) and node.value.id == "self" \
                    and node.attr.isupper():
                self.self_refs.append((node.attr, node.lineno))


def _readme_flags(readme_text: str) -> Tuple[Set[str], Set[str]]:
    """-> (flags mentioned anywhere, flags inside flag-section fences)."""
    anywhere = {f"--{m}" for m in _FLAG_RE.findall(readme_text)}
    fenced: Set[str] = set()
    in_section = in_fence = False
    for line in readme_text.splitlines():
        if _HEADING_RE.match(line):
            in_section = bool(_FLAG_SECTION_RE.match(line))
            in_fence = False
            continue
        if line.startswith("```"):
            in_fence = not in_fence
            continue
        if in_section and in_fence:
            fenced.update(f"--{m}" for m in _FLAG_RE.findall(line))
    return anywhere, fenced


def check_config_drift(config_path: str, readme_path: str,
                       rel_config: str = "code2vec_tpu/config.py",
                       rel_readme: str = "README.md"
                       ) -> List[Finding]:
    """The whole rule as a path-in/findings-out function so fixture
    tests can aim it at a miniature config/README pair."""
    with open(config_path, "r", encoding="utf-8") as f:
        model = ConfigModel(ast.parse(f.read()))
    readme_text = ""
    if os.path.exists(readme_path):
        with open(readme_path, "r", encoding="utf-8") as f:
            readme_text = f.read()
    documented, fenced = _readme_flags(readme_text)
    findings: List[Finding] = []

    def add(line: int, symbol: str, message: str,
            path: str = rel_config) -> None:
        findings.append(Finding(rule=RULE, path=path, line=line,
                                symbol=symbol, message=message))

    for dest, line in model.dests:
        if dest not in model.ns_reads:
            add(line, f"--{dest}",
                f"dead flag: dest '{dest}' is never read in "
                "load_from_args — the flag parses and silently does "
                "nothing")
    dest_names = {d for d, _ in model.dests}
    for read in sorted(model.ns_reads - dest_names):
        add(0, f"ns.{read}",
            f"phantom dest: load_from_args reads ns.{read} but no "
            "add_argument declares it — AttributeError when parsing")
    for attr, line in model.self_refs:
        if attr not in model.fields:
            add(line, f"self.{attr}",
                f"unknown attr: self.{attr} is not a Config dataclass "
                "field (typo'd verify rule guards nothing)")
    for flag, line in model.flags:
        if flag not in documented:
            add(line, flag,
                f"undocumented flag: {flag} is not mentioned anywhere "
                f"in {rel_readme}")
    known_flags = {f for f, _ in model.flags}
    for flag in sorted(fenced - known_flags):
        add(0, flag,
            f"stale doc: {flag} appears in {rel_readme}'s flag docs "
            "but argparse does not accept it", path=rel_readme)
    for field in sorted(model.fields - model.cfg_writes
                        - model.constants):
        add(0, field,
            f"unwired attr: Config.{field} has no CLI path "
            "(load_from_args never assigns it) and is not listed in "
            "CONFIG_CONSTANTS — add a flag or register the constant")
    for name in sorted(model.constants & model.cfg_writes):
        add(0, name,
            f"Config.{name} is listed in CONFIG_CONSTANTS but IS "
            "assigned in load_from_args — drop it from the constants "
            "register")
    for name in sorted(model.constants - model.fields):
        add(0, name,
            f"CONFIG_CONSTANTS names '{name}' which is not a Config "
            "dataclass field")
    return findings


@register
class ConfigDriftRule(Rule):
    name = RULE
    description = ("Config fields <-> argparse flags <-> README docs "
                   "consistency (dead flags, phantom dests, typo'd "
                   "verify attrs, un-/stale-documented flags, unwired "
                   "fields)")

    def check_repo(self, ctxs: Sequence[FileContext],
                   root: str) -> Iterable[Finding]:
        config_path = os.path.join(root, "code2vec_tpu", "config.py")
        if not os.path.exists(config_path):
            return ()
        return check_config_drift(
            config_path, os.path.join(root, "README.md"))
