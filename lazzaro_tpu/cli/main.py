"""Interactive CLI REPL.

Parity target: reference ``cli/main.py`` (153 LoC) — same slash commands
(/start /end /stats /profile /memories [n] /consolidate /merge /prune [t]
/config /set <k> <v> /save [f] /load [f] /users /switch <u> /quit /help),
streaming chat path. Differences by design:
- offline-first: no API key required (HeuristicLLM + HashingEmbedder run on
  device); pass OPENAI_API_KEY + --remote to use the OpenAI shim.
- /save and /load actually work (the reference's reference
  ``memory.persistence.filepath`` crashes — SURVEY §2.2 quirk list).
"""

from __future__ import annotations

import argparse
import os
import sys


def build_memory(args) -> "MemorySystem":
    from lazzaro_tpu.core.memory_system import MemorySystem

    llm = embedder = None
    if args.remote:
        api_key = os.getenv("OPENAI_API_KEY", "")
        if not api_key:
            print("⚠ --remote requires OPENAI_API_KEY; falling back to on-device providers.")
        else:
            from lazzaro_tpu.core.providers import OpenAIEmbedder, OpenAILLM
            llm = OpenAILLM(api_key)
            embedder = OpenAIEmbedder(api_key)
    elif args.encoder:
        from lazzaro_tpu.core.providers import EncoderEmbedder
        embedder = EncoderEmbedder()

    return MemorySystem(
        db_dir=args.db_dir,
        user_id=args.user,
        llm_provider=llm,
        embedding_provider=embedder,
        max_buffer_size=args.max_buffer_size,
        prune_threshold=args.prune_threshold,
    )


HELP = ("Available commands: /start, /end, /stats, /profile, /memories [n], "
        "/consolidate, /merge, /prune [thresh], /config, /set <k> <v>, "
        "/save [file], /load [file], /snapshot [dir], /restore [dir], "
        "/users, /switch <user>, /quit")

CONFIG_PARAMS = ["max_buffer_size", "prune_threshold", "consolidate_every",
                 "auto_consolidate", "auto_prune", "enable_sharding",
                 "enable_hierarchy", "enable_caching", "enable_async"]


def handle_command(memory, user_input: str) -> bool:
    """Process one slash command; returns False when the REPL should exit."""
    parts = user_input.split()
    cmd = parts[0].lower()

    if cmd == "/quit":
        if memory.conversation_active:
            print("\n" + memory.end_conversation())
        print("\n👋 Goodbye!")
        return False
    elif cmd == "/start":
        print("\n" + memory.start_conversation())
    elif cmd == "/end":
        print("\n" + memory.end_conversation())
    elif cmd == "/stats":
        print(memory.display_stats())
    elif cmd == "/profile":
        print(memory.display_profile())
    elif cmd == "/memories":
        limit = int(parts[1]) if len(parts) > 1 else 10
        print(memory.display_memories(limit=limit))
    elif cmd == "/consolidate":
        print("\n" + memory.run_consolidation())
    elif cmd == "/merge":
        print("\n🔄 Merging similar nodes...")
        merged = memory._merge_similar_nodes()
        print(f"✓ Merged {merged} similar nodes")
    elif cmd == "/prune":
        threshold = float(parts[1]) if len(parts) > 1 else memory.prune_threshold
        print(f"\n🔄 Pruning edges below {threshold}...")
        pruned = memory._prune_weak_edges(threshold)
        print(f"✓ Pruned {pruned} weak edges")
    elif cmd == "/config":
        print("\n⚙️ Configuration:")
        for param in CONFIG_PARAMS:
            print(f"  • {param}: {getattr(memory, param)}")
    elif cmd == "/set":
        if len(parts) < 3:
            print("⚠ Usage: /set <parameter> <value>")
            return True
        param, value_str = parts[1], parts[2]
        if not hasattr(memory, param):
            print(f"⚠ Unknown parameter: {param}")
            return True
        try:
            val_type = type(getattr(memory, param))
            if val_type is bool:
                value = value_str.lower() in ("true", "1", "on", "yes")
            else:
                value = val_type(value_str)
            setattr(memory, param, value)
            print(f"✓ Set {param} = {value}")
        except ValueError:
            print(f"⚠ Invalid value for {param}")
    elif cmd == "/save":
        memory._save_to_persistence()
        filename = parts[1] if len(parts) > 1 else "memory_state.json"
        print("\n" + memory.save_state(filename))
    elif cmd == "/load":
        if len(parts) > 1:
            print("\n" + memory.load_state(parts[1]))
        else:
            memory._load_from_persistence()
            print(f"\n✓ Reloaded user '{memory.user_id}' from {memory.config.db_dir}")
    elif cmd == "/snapshot":
        target = parts[1] if len(parts) > 1 else "memory_snapshot"
        print("\n" + memory.save_snapshot(target))
    elif cmd == "/restore":
        target = parts[1] if len(parts) > 1 else "memory_snapshot"
        print("\n" + memory.load_snapshot(target))
    elif cmd == "/users":
        for u in memory.get_all_users():
            marker = " ←" if u == memory.user_id else ""
            print(f"  • {u}{marker}")
    elif cmd == "/switch":
        if len(parts) < 2:
            print("⚠ Usage: /switch <user_id>")
        else:
            memory.switch_user(parts[1])
    elif cmd == "/help":
        print(HELP)
    else:
        print(f"⚠ Unknown command: {cmd}. Try /help")
    return True


def interactive_chat(args=None) -> None:
    args = args or parse_args([])
    print("=" * 60)
    print("  LAZZARO-TPU MEMORY SYSTEM — CLI")
    print("=" * 60)
    print("\n" + HELP)

    memory = build_memory(args)
    while True:
        try:
            user_input = input("\nYou: ").strip()
            if not user_input:
                continue
            if user_input.startswith("/"):
                if not handle_command(memory, user_input):
                    break
            else:
                first = True
                print("Assistant: ", end="", flush=True)
                for event in memory.chat_stream(user_input):
                    if event["type"] == "token":
                        print(event["content"], end="", flush=True)
                        first = False
                    elif event["type"] == "info" and first:
                        print(f"\n{event['content']}")
                print()
        except (KeyboardInterrupt, EOFError):
            print("\n👋 Goodbye!")
            break
        except Exception as e:  # keep the REPL alive (parity :146-147)
            print(f"\n⚠ Error: {e}")
    memory.close()


def parse_args(argv):
    p = argparse.ArgumentParser(prog="lazzaro-tpu-cli",
                                description="TPU-native memory system REPL")
    p.add_argument("--db-dir", default="db")
    p.add_argument("--user", default="default")
    p.add_argument("--max-buffer-size", type=int, default=10)
    p.add_argument("--prune-threshold", type=float, default=0.5)
    p.add_argument("--remote", action="store_true",
                   help="use OpenAI providers (needs OPENAI_API_KEY)")
    p.add_argument("--encoder", action="store_true",
                   help="use the on-TPU flax encoder for embeddings")
    return p.parse_args(argv)


def main() -> None:
    from lazzaro_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    interactive_chat(parse_args(sys.argv[1:]))


if __name__ == "__main__":
    main()
